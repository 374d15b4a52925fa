"""Single-group Raft consensus core, reference-semantics.

This package is the host-side oracle for the batched TPU engine: it
reproduces the observable behavior (message sequences, Ready contents, log
lines) of the reference implementation (ref: raft/raft.go and friends) and
must replay raft/testdata interaction traces bit-for-bit.

The hot arithmetic (quorum order statistics, vote tallies, log term
matching) is factored into small pure functions so the batched engine in
``etcd_tpu.batched`` can reuse the same definitions under vmap.
"""

from .types import (  # noqa: F401
    Entry,
    EntryType,
    HardState,
    Message,
    MessageType,
    Snapshot,
    SnapshotMetadata,
    ConfState,
    ConfChange,
    ConfChangeV2,
    ConfChangeSingle,
    ConfChangeType,
    ConfChangeTransition,
    EMPTY_HARD_STATE,
    is_empty_hard_state,
    is_empty_snap,
)
from .errors import (  # noqa: F401
    CompactedError,
    UnavailableError,
    SnapOutOfDateError,
    SnapshotTemporarilyUnavailableError,
    ProposalDroppedError,
    StepLocalMsgError,
    StepPeerNotFoundError,
)
from .storage import MemoryStorage, Storage  # noqa: F401
from .raft import Config, Raft, StateType, ReadOnlyOption, NONE  # noqa: F401
from .rawnode import RawNode, Ready, SoftState, ReadState  # noqa: F401
