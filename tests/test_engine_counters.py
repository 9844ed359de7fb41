"""``ContinuousEngine.get_metrics()`` by name: the keys a spec reports, and
the keys of its ``mla`` / ``attn`` / ``state`` / ``moe`` groups, written down
from the output of the commit before the counters went by name (PR 44's
parent), for the seven tiny specs whose programs ``scripts/decode_jaxpr.py``
dumps. The worker's counters, ``perfbench/lib/scopes*.py`` and
``obs/collectors.py`` read these keys letter for letter. After a short
``generate()`` every family counter that commit reported non-zero is
non-zero.

    JAX_PLATFORMS=cpu PYTHONPATH=<a checkout> python tests/test_engine_counters.py

prints what a checkout reports, in the form of ``EXPECTED`` below."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.config import EngineConfig  # noqa: E402
from distributed_inference_engine_tpu.engine.continuous import (  # noqa: E402
    ContinuousEngine,
)
from distributed_inference_engine_tpu.engine.types import (  # noqa: E402
    GenerationRequest,
)
from distributed_inference_engine_tpu.models import (  # noqa: E402
    ling_spec, mistral_spec, xing,
)
from distributed_inference_engine_tpu.models.mellum import (  # noqa: E402
    mellum_spec,
)
from distributed_inference_engine_tpu.models.olmo_hybrid import (  # noqa: E402
    olmo_hybrid_spec,
)

GROUPS = ("mla", "attn", "state", "moe")

# name -> (spec, EngineConfig fields beside the shared ones)
SPECS = {
    "mistral-tiny": (
        lambda: mistral_spec("mistral-tiny", sliding_window=0,
                             max_seq_len=128), {}),
    "mistral-tiny-swa": (
        lambda: mistral_spec("mistral-tiny", sliding_window=64,
                             max_seq_len=128), {}),
    "ling-tiny": (lambda: ling_spec("ling-tiny"), {}),
    "xing-tiny": (lambda: xing.xing_spec("xing-tiny", max_seq_len=128), {}),
    "olmo-hybrid-tiny": (lambda: olmo_hybrid_spec("olmo-hybrid-tiny"), {}),
    "mellum-tiny": (
        lambda: mellum_spec("mellum-tiny", max_seq_len=256),
        dict(max_seq_len=256, page_size=8, num_pages=128)),
    "kimi-tiny": (lambda: xing.kimi_spec("kimi-tiny", max_seq_len=128),
                  dict(max_slots=12, num_pages=96)),
}

_TOP = [
    "admission_denied", "admissions", "admissions_ahead",
    "admissions_from_queue", "attn_impl", "batch_occupancy",
    "capacity_finishes", "chunked_admissions", "compiles_after_warmup",
    "deadline_expired", "decode_chunk", "decode_chunks",
    "decode_chunks_dense", "decode_chunks_in_place", "decode_steps",
    "dispatch_s_total", "emit_carried_chunks", "emit_flushed_chunks",
    "empty_slot_dispatches", "engine_steps", "finishes_learned_late",
    "harvest_wait_s_total", "host_bubble_frac", "host_gap_s_total", "kv",
    "live_slots", "mla", "moe", "prefill", "prefill_calls",
    "prefilling_slots", "prefix_disabled_per_layer",
    "prefix_hit_admissions", "queue_wait", "rejected_queue_full",
    "reprefill_preemptions", "residual", "shed_deadline", "slots",
    "stream_clamped_chunks", "sync_fallback_iterations",
    "total_generated_tokens", "total_prompt_tokens", "total_requests",
    "ttft", "waiting", "warmup"]
_MOE = ["assignments_held", "assignments_total", "decode_assignments_held",
        "experts_touched"]
_MLA_DECODE = ["decode_context_rows", "decode_table_rows"]
_MLA = _MLA_DECODE + ["prefill_key_blocks_bucket",
                      "prefill_key_blocks_visited"]
_FULL = ["full_context_rows", "full_prefill_key_blocks_bucket",
         "full_prefill_key_blocks_visited", "full_table_rows"]
_WINDOW = ["window_context_rows", "window_prefill_key_blocks_bucket",
           "window_prefill_key_blocks_visited", "window_table_rows"]
_STATE = ["rows_updated", "step_body"]


def _want(extra_top=(), mla=_MLA_DECODE, attn=None, state=None,
          nonzero=()):
    return {"top": sorted(_TOP + list(extra_top)), "mla": mla, "attn": attn,
            "state": state, "moe": _MOE, "nonzero": sorted(nonzero)}


_MLA_MOE_NONZERO = [f"mla.{k}" for k in _MLA] + [f"moe.{k}" for k in _MOE]

# what PR 44's parent (90c7895) reports
EXPECTED = {
    "mistral-tiny": _want(),
    "mistral-tiny-swa": _want(),
    "ling-tiny": _want(
        ("state",), mla=_MLA, state=_STATE,
        nonzero=_MLA_MOE_NONZERO + ["state.rows_updated"]),
    "xing-tiny": _want(mla=_MLA, nonzero=_MLA_MOE_NONZERO),
    "olmo-hybrid-tiny": _want(
        ("attn", "state"), attn=_FULL, state=_STATE,
        nonzero=[f"attn.{k}" for k in _FULL] + ["state.rows_updated"]),
    "mellum-tiny": _want(
        ("attn",), attn=sorted(_FULL + _WINDOW),
        nonzero=[f"attn.{k}" for k in _FULL + _WINDOW]
        + [f"moe.{k}" for k in _MOE]),
    "kimi-tiny": _want(mla=_MLA, nonzero=_MLA_MOE_NONZERO),
}


def observe(name):
    """What ``name``'s engine reports after three short requests."""
    make, kw = SPECS[name]
    cfg = dict(max_slots=4, max_seq_len=128, page_size=16, num_pages=40,
               prefill_buckets=[32, 64], decode_steps_per_call=4)
    cfg.update(kw)
    eng = ContinuousEngine(make(), config=EngineConfig(**cfg), seed=3)
    rng = np.random.default_rng(2)
    results = eng.generate([GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, 256, 20 + 9 * i)],
        max_new_tokens=10) for i in range(3)])
    assert [len(r.tokens) for r in results] == [10, 10, 10]
    m = eng.get_metrics()
    got = {"top": sorted(m)}
    nonzero = []
    for g in GROUPS:
        got[g] = sorted(m[g]) if g in m else None
        nonzero += [f"{g}.{k}" for k, v in m.get(g, {}).items()
                    if isinstance(v, int) and v]
    got["nonzero"] = sorted(nonzero)
    return got


@pytest.mark.parametrize("name", sorted(SPECS))
def test_metric_keys_and_live_counters_are_the_parents(name):
    assert observe(name) == EXPECTED[name]


if __name__ == "__main__":
    print(json.dumps({n: observe(n) for n in sorted(SPECS)}, indent=1))
