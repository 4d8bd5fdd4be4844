"""torchsnapshot_tpu: a TPU-native, memory-budgeted, distributed
checkpointing framework for JAX.

Brand-new implementation with the capabilities of
facebookresearch/torchsnapshot, re-designed for TPU/XLA:

- zero-copy host-buffer serialization (bfloat16/fp8 first-class),
- overlapped XLA device→host transfer and storage I/O under an explicit
  host-memory budget,
- collective-free write partitioning for sharded/replicated ``jax.Array``s
  (sharding layouts are global knowledge in SPMD JAX),
- async snapshots that unblock training as soon as staging completes, with
  a KV-only background commit,
- automatic resharding (elasticity) across meshes/world sizes on restore,
- random access to individual snapshot objects under a memory budget.
"""

from . import knobs, obs, resilience  # noqa: F401
from .coordination import (  # noqa: F401
    Coordinator,
    FileCoordinator,
    JaxCoordinator,
    LocalCoordinator,
    get_default_coordinator,
)
from .continuous import (  # noqa: F401
    ContinuousCheckpointer,
    recover_state,
)
from .event import Event  # noqa: F401
from .event_handlers import register_event_handler, unregister_event_handler  # noqa: F401
from .manager import SnapshotManager, delete_snapshot  # noqa: F401
from .publish import (  # noqa: F401
    LiveWeights,
    Publisher,
    Subscriber,
)
from .tier import (  # noqa: F401
    TierConfig,
    TieredStoragePlugin,
    drain_promotions,
)
from .resilience import SnapshotAbortedError  # noqa: F401
from .verify import VerifyResult, verify_snapshot  # noqa: F401
from .snapshot import PendingSnapshot, Snapshot  # noqa: F401
from .stateful import (  # noqa: F401
    PyTreeState,
    Replicated,
    RNGState,
    StateDict,
    Stateful,
)

__version__ = "0.2.0"

__all__ = [
    "Snapshot",
    "PendingSnapshot",
    "SnapshotManager",
    "delete_snapshot",
    "TierConfig",
    "TieredStoragePlugin",
    "drain_promotions",
    "ContinuousCheckpointer",
    "recover_state",
    "Publisher",
    "Subscriber",
    "LiveWeights",
    "SnapshotAbortedError",
    "VerifyResult",
    "verify_snapshot",
    "resilience",
    "Stateful",
    "StateDict",
    "PyTreeState",
    "Replicated",
    "RNGState",
    "Coordinator",
    "LocalCoordinator",
    "JaxCoordinator",
    "FileCoordinator",
    "get_default_coordinator",
    "Event",
    "register_event_handler",
    "unregister_event_handler",
    "knobs",
    "obs",
]
