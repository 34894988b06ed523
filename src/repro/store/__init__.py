"""Versioned artifact store: content-addressed objects + delta chains,
storage-graph optimization via the paper's solvers."""

from .delta import (
    DeltaWire,
    RecreationCostModel,
    SparseLeafDelta,
    apply_delta_chain,
    apply_delta_chains,
    decode_delta_wire,
    decode_full,
    encode_delta,
    encode_full,
    flatten_payload,
)
from .materializer import (
    CheckoutPlan,
    CheckoutPlanner,
    MaterializationCache,
    Materializer,
)
from .objectstore import Codec, ObjectStore
from .repository import Ref, Repository, TreeDiff
from .version_store import VersionMeta, VersionStore

__all__ = [
    "Codec",
    "ObjectStore",
    "VersionStore",
    "VersionMeta",
    "Repository",
    "TreeDiff",
    "Ref",
    "Materializer",
    "MaterializationCache",
    "CheckoutPlanner",
    "CheckoutPlan",
    "RecreationCostModel",
    "flatten_payload",
    "encode_full",
    "decode_full",
    "encode_delta",
    "apply_delta_chain",
    "apply_delta_chains",
    "decode_delta_wire",
    "DeltaWire",
    "SparseLeafDelta",
]
