"""tpu_dist.dist — process groups, rendezvous, stores (L1 of SURVEY.md §1).

The c10d equivalent: ``init_process_group`` and friends
(/root/reference/mpspawn_dist.py:49-54, README.md:36-43), redesigned for the
TPU topology (one process per host, a mesh of cores, XLA collectives).
"""

from .process_group import (DATA_AXIS, BackendMismatchError, ProcessGroup,
                            abort, barrier, monitored_barrier,
                            destroy_process_group, get_backend,
                            get_default_group, get_local_rank,
                            get_local_world_size, get_num_processes,
                            get_rank, get_world_size, init_process_group,
                            is_initialized, new_group, resolve_backend)
from .rendezvous import generation, get_store, parse_init_method, rendezvous
from .store import Store, TCPStore, FileStore
from ..collectives.eager import ReduceOp  # torch `dist.ReduceOp` parity

__all__ = [
    "ProcessGroup", "init_process_group", "destroy_process_group",
    "is_initialized", "get_default_group", "get_world_size", "get_rank",
    "get_backend", "resolve_backend", "BackendMismatchError",
    "get_local_rank", "get_local_world_size", "get_num_processes",
    "new_group", "barrier", "monitored_barrier", "abort", "DATA_AXIS",
    "rendezvous", "parse_init_method", "generation", "get_store",
    "Store", "TCPStore", "FileStore", "ReduceOp",
]
