from dlrover_tpu.data.coworker import CoworkerDataLoader
from dlrover_tpu.data.prefetch import (
    Prefetcher,
    make_input_pipeline,
    prefetch_depth,
)
from dlrover_tpu.data.shm_ring import ShmBatchRing

__all__ = [
    "CoworkerDataLoader",
    "Prefetcher",
    "ShmBatchRing",
    "make_input_pipeline",
    "prefetch_depth",
]
