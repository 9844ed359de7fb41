"""The benchmark's yardstick: traffic, load generation, metric arithmetic,
trace reduction, peaks and operation counts. Nothing here imports jax at
import time; only ``reference`` and the trace reader do, in child processes."""
