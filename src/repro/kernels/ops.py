"""Public jit'd wrappers over the Pallas kernels.

Backends: ``'pallas'`` runs the kernels compiled for the TPU and refuses to
run anywhere else; ``'pallas_interpret'`` evaluates the same kernel bodies
in Pallas interpret mode (the CPU differential tests); ``'xla'`` selects
the pure-jnp reference twins — identical math, XLA-fused.  ``'auto'``
picks pallas on a TPU and xla elsewhere.

Routing is one spec-keyed dispatch table (``_KERNELS``): every operator
stage maps (spec name, stage) → (jnp reference twin, Pallas kernel), and
``kernel_call`` resolves the backend once for all of them — the previous
ten hand-rolled routing shims collapsed to entries.  The named wrappers
below are kept as the stable public API (and document each stage's
contract); each is a one-line table dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref as _ref
from .rtree_join import join_level_fused as _join_fused_pallas
from .rtree_join import join_pair_masks as _join_pallas
from .rtree_knn import knn_leaf_fused as _knn_leaf_fused_pallas
from .rtree_knn import knn_level_dists as _knn_pallas
from .rtree_knn import knn_level_dists_d3 as _knn_d3_pallas
from .rtree_knn import knn_level_fused as _knn_fused_pallas
from .rtree_knn_join import knn_join_leaf_fused as _knn_join_leaf_fused_pallas
from .rtree_knn_join import knn_join_level_dists as _knn_join_pallas
from .rtree_knn_join import knn_join_level_dists_d3 as _knn_join_d3_pallas
from .rtree_knn_join import knn_join_level_fused as _knn_join_fused_pallas
from .rtree_select import select_level_fused as _select_fused_pallas
from .rtree_select import select_level_fused_d3 as _select_fused_d3_pallas
from .rtree_select import select_level_masks as _select_pallas
from .rtree_select import select_level_masks_d3 as _select_d3_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "xla"
    if backend not in ("pallas", "xla", "pallas_interpret"):
        raise ValueError(backend)
    return backend


def _join_level_fused_ref(o_ids, i_ids, alive_cnt, flip_max, o_coords,
                          i_coords, o_ptr, i_ptr, *, cap: int, to: int = 8):
    # the jnp twin needs the inner tile width pinned to the kernel's
    return _ref.join_level_fused_ref(
        o_ids, i_ids, alive_cnt, flip_max, o_coords, i_coords, o_ptr, i_ptr,
        cap=cap, to=to, ti=min(128, i_coords.shape[2]))


# (spec name, stage) → (jnp reference twin, Pallas kernel).  'score' is the
# unfused level evaluation; 'fused' / 'fused_leaf' are the whole-level
# programs with in-kernel emission (``fused=True`` operator paths) whose
# xla twins are the bit-compatible differential references the Pallas
# kernels are swept against.
_KERNELS = {
    ("select", "score"): (_ref.select_level_masks_ref, _select_pallas),
    ("select", "fused"): (_ref.select_level_fused_ref, _select_fused_pallas),
    ("select", "score_d3"): (_ref.select_level_masks_d3_ref,
                             _select_d3_pallas),
    ("select", "fused_d3"): (_ref.select_level_fused_d3_ref,
                             _select_fused_d3_pallas),
    ("knn", "score"): (_ref.knn_level_dists_ref, _knn_pallas),
    ("knn", "score_d3"): (_ref.knn_level_dists_d3_ref, _knn_d3_pallas),
    ("knn", "fused"): (_ref.knn_level_fused_ref, _knn_fused_pallas),
    ("knn", "fused_leaf"): (_ref.knn_leaf_fused_ref, _knn_leaf_fused_pallas),
    ("knn_join", "score"): (_ref.knn_join_level_dists_ref, _knn_join_pallas),
    ("knn_join", "score_d3"): (_ref.knn_join_level_dists_d3_ref,
                               _knn_join_d3_pallas),
    ("knn_join", "fused"): (_ref.knn_join_level_fused_ref,
                            _knn_join_fused_pallas),
    ("knn_join", "fused_leaf"): (_ref.knn_join_leaf_fused_ref,
                                 _knn_join_leaf_fused_pallas),
    ("join", "score"): (_ref.join_pair_masks_ref, _join_pallas),
    ("join", "fused"): (_join_level_fused_ref, _join_fused_pallas),
}


# Which (layout, fused) cells each operator's kernel backends implement —
# mirrors the engine guards: the level-global D1 SoA arrays have the full
# kernel column, the quantized D3 streams have score kernels for
# select/knn/knn_join plus the fused select variant, every other layout is
# jnp-only (and knn_filtered has no kernel backend at all).
KERNEL_CELLS = {
    "select": {("d1", False), ("d1", True), ("d3", False), ("d3", True)},
    "join": {("d1", False), ("d1", True)},
    "knn": {("d1", False), ("d1", True), ("d3", False)},
    "knn_join": {("d1", False), ("d1", True), ("d3", False)},
    "knn_filtered": set(),
}


def kernel_call(op: str, stage: str, *args, backend: str = "auto", **kwargs):
    """Dispatch one operator stage to its jnp twin (backend 'xla'), its
    Pallas kernel compiled for the TPU ('pallas'), or the same kernel in
    interpret mode ('pallas_interpret')."""
    ref_fn, pallas_fn = _KERNELS[(op, stage)]
    b = resolve_backend(backend)
    if b == "xla":
        return ref_fn(*args, **kwargs)
    if b == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"backend='pallas' runs the {op}/{stage} kernel compiled for a "
            f"TPU, but JAX's default backend is {jax.default_backend()!r}; "
            f"use 'pallas_interpret' or 'xla' off the chip")
    return pallas_fn(*args, interpret=b == "pallas_interpret", **kwargs)


# ---------------------------------------------------------------------------
# Stable named API (documented contracts; all table dispatches)
# ---------------------------------------------------------------------------

def select_level_masks(ids, queries, lx, ly, hx, hy, child,
                       backend: str = "auto"):
    """BFS level-step qualify masks: (B,C) ids × (B,4) queries → (B,C,F)."""
    return kernel_call("select", "score", ids, queries, lx, ly, hx, hy,
                       child, backend=backend)


def select_level_masks_d3(ids, queries, qlo, qhi, scale, bias, ptr,
                          backend: str = "auto"):
    """Quantized-level qualify masks: (B,C) ids × (B,4) queries over packed
    uint16 code rows → (B,C,F) conservative bitmask (superset of the exact
    D1 mask; the operator re-checks exact geometry at the leaf)."""
    return kernel_call("select", "score_d3", ids, queries, qlo, qhi, scale,
                       bias, ptr, backend=backend)


def select_level_fused_d3(ids, queries, qlo, qhi, scale, bias, ptr, *,
                          cap: int, backend: str = "auto"):
    """Fused quantized select level: streams the packed uint16 code blocks
    and compress-stores qualifying children in-kernel — contract as
    ``select_level_fused``."""
    return kernel_call("select", "fused_d3", ids, queries, qlo, qhi, scale,
                       bias, ptr, cap=cap, backend=backend)


def knn_level_dists_d3(ids, points, qlo, qhi, scale, bias, slack, ptr,
                       backend: str = "auto"):
    """Quantized kNN level distances: → (MINDIST lower bound, slack-
    corrected MINMAXDIST upper bound) each (B,C,F) f32, DIST_PAD on invalid
    lanes.  Internal levels only — leaf rows go through the exact D1
    kernel."""
    return kernel_call("knn", "score_d3", ids, points, qlo, qhi, scale,
                       bias, slack, ptr, backend=backend)


def knn_join_level_dists_d3(ids, qrects, qlo, qhi, scale, bias, slack, ptr,
                            backend: str = "auto"):
    """Quantized kNN-join level pair distances (rect queries): contract as
    ``knn_level_dists_d3``."""
    return kernel_call("knn_join", "score_d3", ids, qrects, qlo, qhi, scale,
                       bias, slack, ptr, backend=backend)


def knn_level_dists(ids, points, lx, ly, hx, hy, child, *,
                    leaf: bool = False, backend: str = "auto"):
    """kNN BFS level-step distances: (B,C) ids × (B,2) points →
    (mindist, minmaxdist) each (B,C,F) f32 with DIST_PAD on invalid lanes.
    ``leaf=True`` selects the leaf-specialized variant (no MINMAXDIST math
    or store) and returns None for the bound."""
    return kernel_call("knn", "score", ids, points, lx, ly, hx, hy, child,
                       leaf=leaf, backend=backend)


def knn_join_level_dists(ids, qrects, lx, ly, hx, hy, child, *,
                         leaf: bool = False, backend: str = "auto"):
    """kNN-join BFS level-step pair distances: (B,C) ids × (B,4) rects →
    (mindist, minmaxdist) each (B,C,F) f32 with DIST_PAD on invalid lanes.
    ``leaf=True`` selects the leaf-specialized variant (no MINMAXDIST math or
    store) and returns None for the bound."""
    return kernel_call("knn_join", "score", ids, qrects, lx, ly, hx, hy,
                       child, leaf=leaf, backend=backend)


def join_pair_masks(o_ids, i_ids, alive_cnt, flip_max, o_coords, i_coords,
                    to: int = 8, ti: int = 128, backend: str = "auto"):
    """Pair-frontier tile masks: (P,) × (P,) node ids → (P, F_o, F_i)."""
    return kernel_call("join", "score", o_ids, i_ids, alive_cnt, flip_max,
                       o_coords, i_coords, to=to, ti=ti, backend=backend)


def select_level_fused(ids, queries, lx, ly, hx, hy, child, *, cap: int,
                       backend: str = "auto"):
    """Fused select level: (B,C) ids × (B,4) queries → (next_ids (B,cap),
    counts (B,), overflow (B,)) — compact_rows' contract, in one step."""
    return kernel_call("select", "fused", ids, queries, lx, ly, hx, hy,
                       child, cap=cap, backend=backend)


def knn_level_fused(ids, points, lx, ly, hx, hy, child, tau, *, cap: int,
                    k: int, tighten: bool, backend: str = "auto"):
    """Fused kNN internal level: → (next_ids (B,cap), τ (B,),
    valid_cnt (B,), keep_cnt (B,))."""
    return kernel_call("knn", "fused", ids, points, lx, ly, hx, hy, child,
                       tau, cap=cap, k=k, tighten=tighten, backend=backend)


def knn_leaf_fused(ids, points, lx, ly, hx, hy, child, *, k: int,
                   backend: str = "auto"):
    """Fused kNN leaf level: → (res_ids (B,k), res_d (B,k), valid_cnt (B,));
    missing neighbours are (-1, +inf)."""
    return kernel_call("knn", "fused_leaf", ids, points, lx, ly, hx, hy,
                       child, k=k, backend=backend)


def knn_join_level_fused(ids, qrects, lx, ly, hx, hy, child, tau, *,
                         cap: int, k: int, tighten: bool,
                         backend: str = "auto"):
    """Fused kNN-join internal level (rect queries): contract as
    ``knn_level_fused``."""
    return kernel_call("knn_join", "fused", ids, qrects, lx, ly, hx, hy,
                       child, tau, cap=cap, k=k, tighten=tighten,
                       backend=backend)


def knn_join_leaf_fused(ids, qrects, lx, ly, hx, hy, child, *, k: int,
                        backend: str = "auto"):
    """Fused kNN-join leaf level (rect queries): contract as
    ``knn_leaf_fused``."""
    return kernel_call("knn_join", "fused_leaf", ids, qrects, lx, ly, hx,
                       hy, child, k=k, backend=backend)


def join_level_fused(o_ids, i_ids, alive_cnt, flip_max, o_coords, i_coords,
                     o_ptr, i_ptr, *, cap: int, to: int = 8,
                     backend: str = "auto"):
    """Fused join level: pair frontier → (out_o (cap,), out_i (cap,), count,
    overflow) — compact_pairs' contract, in one step."""
    return kernel_call("join", "fused", o_ids, i_ids, alive_cnt, flip_max,
                       o_coords, i_coords, o_ptr, i_ptr, cap=cap, to=to,
                       backend=backend)


def join_prune_metadata(o_ids, i_ids, o_coords, i_coords, *, to: int = 8,
                        o3: bool = True, o45: bool = True):
    """XLA pre-pass computing the scalar-prefetch pruning bounds.

    alive_cnt[p] — #leading outer children with low_x <= max inner high_x
                   (monotone under the sort, so a count == the O3 slice).
    flip_max[p,a] — max over the outer tile's rows of the flip index
                   (#inner children with low_x <= outer high_x).
    """
    so, si = jnp.maximum(o_ids, 0), jnp.maximum(i_ids, 0)
    oc, ic = o_coords[so], i_coords[si]
    p, _, fo = oc.shape
    fi = ic.shape[2]
    to_ = min(to, fo)
    na = fo // to_
    if o3:
        max_ihx = ic[:, 2].max(axis=1)                       # (P,)
        alive = (oc[:, 0] <= max_ihx[:, None]).sum(axis=1)   # (P,)
        alive_cnt = alive.astype(jnp.int32)
    else:
        alive_cnt = jnp.full((p,), fo, jnp.int32)
    if o45:
        flip = (ic[:, 0][:, None, :] <= oc[:, 2][:, :, None]).sum(-1)
        flip_max = flip.reshape(p, na, to_).max(axis=2).astype(jnp.int32)
    else:
        flip_max = jnp.full((p, na), fi, jnp.int32)
    return alive_cnt, flip_max
