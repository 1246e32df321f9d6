from repro.storage.checkpoint import CheckpointEngine, place_on_mesh  # noqa: F401
from repro.storage.datapipe import (FileBackedTokens, PipeState,  # noqa: F401
                                    StripedTokenStore, SyntheticTokens,
                                    pipeline_io_requests, pipeline_io_trace)
from repro.storage.kvoffload import KV_SHAPES, KVShape, plan_kv_offload  # noqa: F401
from repro.storage.ssd_model import compare_interfaces, estimate_io, plan_geometry  # noqa: F401
