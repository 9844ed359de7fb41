"""Counts of the dense int4 GQA decoder (the family of a configuration file
that names none): ``lib/opcount.py``'s numbers, unchanged."""

from perfbench.lib import opcount

CACHE = "K and V rows of every layer, per token: 2 x L x Hkv x Dh x itemsize"
SCOPE_READERS = "readers"       # the module under lib/ (lib/families.py)

param_bytes = opcount.param_bytes
kv_bytes_per_token = opcount.kv_bytes_per_token


def weight_matmuls(cfg):
    return [(name, k, n, times, "int4")
            for name, k, n, times in opcount.int4_matmuls(cfg)]
