"""Test config: force an 8-device virtual CPU mesh so multi-device sharding
is testable without TPU hardware (SURVEY.md §4 implication).  Tests never
touch the chip: `JAX_PLATFORMS=cpu` is set before jax is imported.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from fedml_tpu.utils import compile_cache  # noqa: E402  (after env vars)

# Persistent compilation cache: the suite compiles hundreds of XLA programs
# (mesh round programs dominate wall-clock); repeat runs hit the disk cache
# instead of recompiling.  ONE location for the whole test universe, but for
# the multihost workers: ranks of one cluster keep no persistent cache (a
# rank that loads while its peers compile falls out of step).  0.1 s threshold: the suite compiles many hundreds of
# 0.1-0.5 s programs; caching them too trades ~ms of disk lookup for their
# compile CPU.
compile_cache.configure(min_compile_time_secs=0.1)
