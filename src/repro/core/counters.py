"""Algorithmic performance counters.

The paper reports hardware counters (retired instructions, L1-D/LLC misses,
DTLB misses, branch mispredictions).  TPUs expose none of these; per
DESIGN.md §2 we track deterministic *algorithmic* counters whose ratios
reproduce the paper's relative claims:

  nodes_visited      — node accesses ≈ the paper's cold-miss count driver
  predicates         — MBR comparisons issued (× lanes = "instructions")
  vector_ops         — dense vector predicate ops (SIMD instruction analogue)
  enqueued           — frontier/queue insertions (compress-store analogue)
  pruned_outer       — outer entries skipped by O3 slicing
  pruned_inner       — inner entries skipped by O4/O5 shrinking
  masked_waste       — lanes evaluated but masked off (TPU branch-free waste)
  overflow           — frontier/result capacity overflow flag (0/1)
  dispatches         — device-program launches the host loop issues: each
                       pallas_call plus each post-kernel XLA op-stage over a
                       materialized (B, C, F) intermediate counts as one (a
                       pallas_call is opaque to XLA, so every stage after it
                       is a separate round-trip on a real accelerator).  The
                       per-level stage model is the ``StageModel`` each
                       ``OperatorSpec`` owns (core/traversal.py); fused
                       kernels collapse a level to one launch.

Occupancy counters (the adaptive-caps observability surface):

  lanes_live         — per descent step (coarse → fine, fixed ``OCC_STEPS``
                       slots): frontier slots that held a real node/pair
                       when the level was scored, summed over the batch
  lanes_padded       — per descent step: allocated-but-empty frontier slots
                       the engine still paid ``fanout`` compares for.  The
                       live/(live+padded) ratio per step is exactly the
                       padded-work waste the occupancy-adaptive caps policy
                       (core/caps.py) exists to shrink.
  escalations        — overflow escalations taken by a two-tier engine
                       (traversal.make_escalating_engine): batches re-run on
                       the full-caps tier after the tight tier overflowed

``total`` sums many Counters in one compiled program at a fixed arity
(the host fan-out's per-partition sum, distributed/spatial_shard.py).
"""
from __future__ import annotations

import dataclasses
import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

# fixed per-step occupancy slots: every engine writes step s into
# min(s, OCC_STEPS - 1), so Counters from engines over trees of different
# heights (two-phase routing, replica merges, serve aggregation) always
# add/reduce without shape mismatches.  Trees here are far shallower than 8.
OCC_STEPS = 8


def occupancy_zeros() -> jnp.ndarray:
    """A zeroed per-step occupancy vector (int32, ``OCC_STEPS`` slots)."""
    return jnp.zeros((OCC_STEPS,), jnp.int32)


@dataclasses.dataclass(frozen=True)
class StageModel:
    """Per-BFS-level dispatch stage model owned by an ``OperatorSpec``.

    Unfused levels hand (B, C, F) tensors back to XLA, so each emission
    stage is its own launch; fused levels run score→emit inside one
    pallas_call.  ``inner``/``leaf`` are launches per unfused internal/leaf
    level, ``fused`` per fused level (None when the operator has no fused
    generation).  The traversal engine derives ``Counters.dispatches``
    from this model — it is the single source of truth, so an operator
    cannot silently under-count its launches.
    """
    inner: int
    leaf: int
    fused: int | None = None

    def total(self, height: int, *, fused: bool = False,
              descents: int = 1) -> int:
        """Expected dispatch tally for ``descents`` full traversals of a
        ``height``-level tree."""
        if fused:
            if self.fused is None:
                raise ValueError("operator has no fused stage model")
            per = height * self.fused
        else:
            per = (height - 1) * self.inner + self.leaf
        return per * descents


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Counters:
    nodes_visited: jax.Array | int = 0
    predicates: jax.Array | int = 0
    vector_ops: jax.Array | int = 0
    enqueued: jax.Array | int = 0
    pruned_outer: jax.Array | int = 0
    pruned_inner: jax.Array | int = 0
    masked_waste: jax.Array | int = 0
    overflow: jax.Array | int = 0
    branches: jax.Array | int = 0    # conditional branch points (scalar
                                     # variants only -- TPU code is
                                     # branch-free; paper S3 logical/bitwise)
    dispatches: jax.Array | int = 0  # device-program launches (per-spec
                                     # StageModel above)
    lanes_live: jax.Array | int = 0      # per-step live frontier slots
                                         # ((OCC_STEPS,) int32 from engines;
                                         # scalar 0 until an engine writes)
    lanes_padded: jax.Array | int = 0    # per-step padded frontier slots
    escalations: jax.Array | int = 0     # two-tier overflow escalations

    def tree_flatten(self):
        f = dataclasses.fields(self)
        return tuple(getattr(self, x.name) for x in f), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __add__(self, other: "Counters") -> "Counters":
        return Counters(*[a + b for a, b in zip(self.tree_flatten()[0],
                                                other.tree_flatten()[0])])

    def asdict(self) -> dict:
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, int):
                out[f.name] = v
            else:
                a = np.asarray(v)
                out[f.name] = a.astype(np.int64).tolist() if a.ndim \
                    else int(a)
        return out

    def occupancy(self) -> float:
        """Fraction of frontier slots that were live across all recorded
        steps (1.0 when no engine recorded occupancy)."""
        live = float(np.asarray(self.lanes_live).sum())
        padded = float(np.asarray(self.lanes_padded).sum())
        total = live + padded
        return live / total if total else 1.0

    def validate_dispatches(self, stage_model: StageModel, height: int, *,
                            fused: bool = False,
                            descents: int = 1) -> "Counters":
        """Assert the recorded dispatch tally matches the owning spec's
        stage model (``stage_model.total``) — catches a new operator that
        silently under-counts its device-program launches."""
        expected = stage_model.total(height, fused=fused, descents=descents)
        got = int(self.dispatches)
        if got != expected:
            raise AssertionError(
                f"dispatch tally {got} != stage model "
                f"{expected} (height={height}, fused={fused}, "
                f"descents={descents}, model={stage_model})")
        return self


def zeros() -> Counters:
    z = jnp.zeros((), jnp.int64) if jax.config.jax_enable_x64 else jnp.zeros((), jnp.int32)
    return Counters(*([z] * len(dataclasses.fields(Counters))))


@jax.jit
def _sum(ctrs):
    return functools.reduce(operator.add, ctrs)


# one zero per leaf signature (aval: shape, dtype, weak type; and sharding)
_ZERO_LEAVES: dict = {}


def _zero_like(leaf):
    if not isinstance(leaf, jax.Array):
        return 0
    key = (leaf.aval, leaf.sharding)
    zero = _ZERO_LEAVES.get(key)
    if zero is None:
        zero = _ZERO_LEAVES[key] = jnp.zeros_like(leaf)
    return zero


def total(ctrs, arity: int) -> Counters:
    """The sum of ``ctrs`` as one device program, not one eager add per
    field per term.  The terms are padded to ``arity`` with zeros shaped
    like the first term's leaves, so every count up to ``arity`` of terms
    with one leaf signature runs the same compiled trace.  Exact integer
    sums: equal, field by field, to folding ``+`` over ``ctrs``."""
    ctrs = tuple(ctrs)
    pad = Counters(*[_zero_like(v) for v in ctrs[0].tree_flatten()[0]])
    return _sum(ctrs + (pad,) * (arity - len(ctrs)))
