"""fedml_tpu.scale — the million-client serving spine (ISSUE 10).

Sharded O(1)-per-round client registry, streaming cohort samplers over
its eligibility mask, on-demand client-shard stores, trace-driven
arrival processes, and the virtual-time serve simulation
(`serve.run_serve_sim`).
"""
from fedml_tpu.scale.arrivals import (ARRIVAL_MODES, ArrivalConfig,
                                      ArrivalProcess, ConstantArrivals,
                                      DiurnalArrivals, FlashCrowdArrivals,
                                      TraceArrivals, make_arrivals)
from fedml_tpu.scale.registry import (BANNED, BYTES_PER_CLIENT, CRASHED,
                                      DEAD, FREE, IN_FLIGHT,
                                      ClientRegistry)
from fedml_tpu.scale.sampler import SAMPLER_MODES, StreamingCohortSampler
from fedml_tpu.scale.serve import run_serve_sim, rss_bytes
from fedml_tpu.scale.shardstore import (GeneratorShardStore,
                                        MaterializedShardStore,
                                        MmapShardStore, ShardStore)

__all__ = [
    "ARRIVAL_MODES", "ArrivalConfig", "ArrivalProcess",
    "ConstantArrivals", "DiurnalArrivals", "FlashCrowdArrivals",
    "TraceArrivals", "make_arrivals",
    "BANNED", "BYTES_PER_CLIENT", "CRASHED", "DEAD", "FREE", "IN_FLIGHT",
    "ClientRegistry",
    "SAMPLER_MODES", "StreamingCohortSampler",
    "run_serve_sim", "rss_bytes",
    "GeneratorShardStore", "MaterializedShardStore", "MmapShardStore",
    "ShardStore",
]
