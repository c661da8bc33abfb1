"""CRDT-type adapters: how the core (de)serializes a state type and its ops.

The reference core is generic over ``S: CmRDT + CvRDT + Serialize`` with op
encoding via serde (lib.rs:189-197); here an adapter bundles the same
knowledge for dynamically chosen state types, plus the *accelerator* —
the pluggable execution backend for the two hot paths (per-op fold and
state merge).  ``HostAccelerator`` is the plain loop; the TPU accelerator
(crdt_enc_tpu/parallel/accel.py) batches onto the device kernels.

``CrdtAdapter.state_pack`` is the one optional member: the canonical bytes
of a state, ``codec.pack(state_to_obj(state))`` byte for byte, made without
building the object where the adapter knows how.  A seal that plans no
delta link and a checkpoint of a state with no columnar format take the
state's bytes from it (``core/core.py`` ``_plan_seal``,
``_pack_checkpoint_state``: checkpoint format 2).  An adapter that says
nothing gets exactly that expression; ``lwwmap_adapter()`` packs the live
``entries`` dict, which holds what ``to_obj`` would copy
(``models/lwwmap.py`` "The entries invariant").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from ..models import (
    CrdtMap,
    EmptyCrdt,
    GCounter,
    GSet,
    LWWMap,
    LWWOp,
    LWWReg,
    LWWRegOp,
    MerkleNode,
    MerkleReg,
    MVReg,
    MVRegOp,
    ORSet,
    PNCounter,
    SeqList,
    VClock,
)
from ..utils import codec
from ..models.orset import op_from_obj as orset_op_from_obj
from ..models.seqlist import op_from_obj as seqlist_op_from_obj
from ..models.vclock import Dot


class HostAccelerator:
    """Reference execution: sequential host loops (the thing the TPU path
    replaces — HOT LOOPS #1/#2, reference lib.rs:458-466, 533-539)."""

    def fold_ops(self, state, ops: list):
        for op in ops:
            state.apply(op)
        return state

    def merge_states(self, state, others: list):
        for other in others:
            state.merge(other)
        return state

    def fold_payloads(self, state, payloads: list, actors_hint=()) -> bool:
        """Fold raw decrypted op-file payloads (msgpack op arrays) without
        per-op Python objects.  Returns True if handled; False tells the
        caller to decode and use ``fold_ops`` (this host reference always
        declines — the bulk path lives in the TPU accelerator)."""
        return False


@dataclass
class CrdtAdapter:
    name: bytes
    new: Callable[[], object]
    state_to_obj: Callable = field(default=lambda s: s.to_obj())
    state_from_obj: Callable = None  # type: ignore[assignment]
    op_to_obj: Callable = field(default=lambda op: op.to_obj())
    op_from_obj: Callable = field(default=lambda obj: obj)
    # state -> codec.pack(state_to_obj(state)), without the object where
    # the adapter can (module docstring); None: that expression itself
    state_pack: Callable = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.state_pack is None:
            self.state_pack = lambda s: codec.pack(self.state_to_obj(s))


def gcounter_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"gcounter",
        new=GCounter,
        state_from_obj=GCounter.from_obj,
        op_from_obj=Dot.from_obj,
    )


def pncounter_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"pncounter",
        new=PNCounter,
        state_from_obj=PNCounter.from_obj,
        op_to_obj=lambda op: [op[0], op[1].to_obj()],
        op_from_obj=lambda obj: (int(obj[0]), Dot.from_obj(obj[1])),
    )


def orset_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"orset",
        new=ORSet,
        state_from_obj=ORSet.from_obj,
        op_from_obj=orset_op_from_obj,
    )


def lwwmap_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"lwwmap",
        new=LWWMap,
        state_from_obj=LWWMap.from_obj,
        op_from_obj=LWWOp.from_obj,
        state_pack=lambda s: codec.pack(s.entries),
    )


def mvreg_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"mvreg",
        new=MVReg,
        state_from_obj=MVReg.from_obj,
        op_to_obj=lambda op: [op.clock.to_obj(), op.value],
        op_from_obj=lambda obj: MVRegOp(VClock.from_obj(obj[0]), obj[1]),
    )


def gset_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"gset",
        new=GSet,
        state_from_obj=GSet.from_obj,
        op_to_obj=lambda op: op,  # the op IS the member
        op_from_obj=lambda obj: obj,
    )


def lwwreg_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"lwwreg",
        new=LWWReg,
        state_from_obj=LWWReg.from_obj,
        op_from_obj=LWWRegOp.from_obj,
    )


def merklereg_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"merklereg",
        new=MerkleReg,
        state_from_obj=MerkleReg.from_obj,
        op_from_obj=MerkleNode.from_obj,
    )


def list_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"list",
        new=SeqList,
        state_from_obj=SeqList.from_obj,
        op_from_obj=seqlist_op_from_obj,
    )


def map_adapter(child: bytes = b"orset") -> CrdtAdapter:
    """Causal reset-remove map with nested CRDT values of type ``child``
    (one of crdtmap.CHILD_TYPES)."""
    proto = CrdtMap(child=child)  # op codec needs only the child type
    return CrdtAdapter(
        name=b"map+" + child,
        new=lambda: CrdtMap(child=child),
        state_from_obj=CrdtMap.from_obj,
        op_to_obj=proto.op_to_obj,
        op_from_obj=proto.op_from_obj,
    )


def empty_adapter() -> CrdtAdapter:
    return CrdtAdapter(
        name=b"empty",
        new=EmptyCrdt,
        state_from_obj=EmptyCrdt.from_obj,
        op_to_obj=lambda op: None,
        op_from_obj=lambda obj: None,
    )
