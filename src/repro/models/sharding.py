"""GSPMD sharding rules: param-path -> PartitionSpec.

Strategy (DESIGN.md §4): FSDP over the data(+pod) axes on one weight dim,
TP over ``model`` on the heads/ffn/vocab dim; GSPMD padding absorbs
non-divisible head counts (paligemma 8H, command-r 96H on a 16-way axis).
Activations: batch over data(+pod); heads/d_ff/vocab over model; optional
sequence-parallel residuals.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(mesh):
    """``mesh`` with every axis ``Auto``.

    The rules here are GSPMD constraints and ``shard_map`` specs, which
    need Auto axes; ``jax.make_mesh`` makes Explicit ones by default, and
    an Explicit mesh turns every sharding built on it into a type that
    JAX propagates (and rejects) op by op.  Every sharding and mesh this
    repo hands to JAX goes through here, so callers may pass either."""
    if not isinstance(mesh, Mesh) or all(
            t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(auto_mesh(mesh), spec)


def auto_typed(tree: Any) -> Any:
    """Inside a trace: re-type leaves placed on a mesh with Explicit axes
    (e.g. a caller's ``NamedSharding(jax.make_mesh(...), P())``) onto the
    same mesh with Auto axes, so they combine with the Auto-typed arrays
    of ``named`` shardings.  Leaves already Auto pass through."""
    def one(x):
        s = jax.typeof(x).sharding
        mesh = getattr(s, "mesh", None)
        if mesh is None or not mesh.explicit_axes:
            return x
        auto = mesh.update_axis_types(
            {a: AxisType.Auto for a in mesh.explicit_axes})
        return jax.sharding.reshard(x, NamedSharding(auto, s.spec))

    return jax.tree.map(one, tree)


def fsdp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axes_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on any dim whose size isn't divisible by its mesh-axes
    product (jit in_shardings demand exact divisibility; GSPMD pads only
    intermediates).  E.g. 8 kv-heads on a 16-way model axis -> replicated."""
    fitted = []
    for dim, entry in zip(shape, spec):
        fitted.append(entry if dim % _axes_size(mesh, entry) == 0 else None)
    return P(*fitted)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return fsdp_axes(mesh)


def _param_spec(path: str, leaf, fsdp) -> P:
    """Rules keyed on parameter path substrings (see models/model.py trees)."""
    nd = leaf.ndim
    f = fsdp

    def strip_stack(spec: P) -> P:
        # stacked (scanned) leaves carry a leading layer dim -> None
        return spec

    if "unembed" in path:          # must precede the "embed" substring test
        # Measured (kimi train GA4): P(None, "model") — the "obvious"
        # zero-forward-comms choice — replicates the unembed grads and
        # moments, costing +23 s memory-term and +25 GiB peak vs sharding
        # D over model and V over fsdp.  Keep the measured-better layout.
        return P("model", f)
    if "embed" in path:
        return P("model", f)
    if "norm" in path or "a_param" in path or "gate_vec" in path:
        return P(*([None] * nd))
    if "inner" in path:
        # attention
        if path.endswith("wq") or path.endswith("wk") or path.endswith("wv"):
            if nd >= 3:
                return P(*([None] * (nd - 3)), f, "model", None)
        if path.endswith("wo") and nd >= 3:
            return P(*([None] * (nd - 3)), "model", None, f)
        # rglru / mlstm projections
        if path.endswith("w_x") or path.endswith("w_gate") or path.endswith("w_up"):
            return P(*([None] * (nd - 2)), f, "model")
        if path.endswith("w_out") or path.endswith("w_down"):
            return P(*([None] * (nd - 2)), "model", f)
        if path.endswith("conv_w"):
            return P(*([None] * (nd - 1)), "model")
        if path.endswith("w_input_gate") or path.endswith("w_a_gate"):
            return P(*([None] * (nd - 1)), "model")
        if path.endswith("w_if"):
            return P(*([None] * (nd - 3)), "model", None, None)
        if path.endswith("w_in"):                      # slstm [D, 4, D]
            return P(*([None] * (nd - 3)), f, None, "model")
        if path.endswith("/r"):
            return P(*([None] * nd))
    if "ffn" in path:
        if path.endswith("router"):
            return P(*([None] * (nd - 2)), f, None)
        if path.endswith("wi") or path.endswith("wg"):   # [E, D, F]
            if _CTX.get("moe_ep"):
                # resident-expert EP: experts live whole on their shard
                # (E over dp axes, D/F over model) -> token all-to-all
                # replaces per-microbatch expert-weight all-gathers
                return P(*([None] * (nd - 3)), f, "model", None)
            return P(*([None] * (nd - 3)), "model", f, None)
        if path.endswith("wo") and nd >= 3:              # [E, F, D]
            if _CTX.get("moe_ep"):
                return P(*([None] * (nd - 3)), f, None, "model")
            return P(*([None] * (nd - 3)), "model", None, f)
        if path.endswith("w_up") or path.endswith("w_gate"):
            return P(*([None] * (nd - 2)), f, "model")
        if path.endswith("w_down"):
            return P(*([None] * (nd - 2)), "model", f)
        if path.endswith("w_in"):
            return P(*([None] * (nd - 3)), f, None, "model")
    # default: replicate
    return P(*([None] * nd))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(mesh: Mesh, params: Any) -> Any:
    """NamedSharding pytree matching an (abstract) param tree.

    Stacked leaves (leading layer dim from the scan) get their rule applied
    to the trailing dims — the rules above already index from the right."""
    f = fsdp_axes(mesh)

    def one(path, leaf):
        spec = _param_spec(_path_str(path), leaf, f)
        if len(spec) < leaf.ndim:           # pad leading dims (layer stack)
            spec = P(*([None] * (leaf.ndim - len(spec))), *spec)
        return named(mesh, fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def cache_shardings(mesh: Mesh, caches: Any) -> Any:
    """KV caches: batch over dp; kv-heads over model when divisible, else
    sequence over model (split-K / FlashDecoding-style decode attention —
    GSPMD inserts the psum over sequence shards)."""
    dp = dp_axes(mesh)

    def one(path, leaf):
        p = _path_str(path)
        nd = leaf.ndim
        if p.endswith("/k") or p.endswith("/v"):
            # [(layers,) B, S, Hkv, dh]
            lead = [None] * (nd - 4)
            for cand in (P(*lead, dp, None, "model", None),
                         P(*lead, dp, "model", None, None),
                         P(*lead, dp, None, None, None)):
                if cand == fit_spec(cand, leaf.shape, mesh):
                    return named(mesh, cand)
        if p.endswith("_scale"):
            # [(layers,) B, S, Hkv]
            lead = [None] * (nd - 3)
            for cand in (P(*lead, dp, None, "model"),
                         P(*lead, dp, "model", None),
                         P(*lead, dp, None, None)):
                if cand == fit_spec(cand, leaf.shape, mesh):
                    return named(mesh, cand)
        if nd >= 2:
            spec = P(*([None] * (nd - 2)), dp, "model")
        else:
            spec = P(*([None] * nd))
        return named(mesh, fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, caches)


def batch_shardings(mesh: Mesh, batch: Any) -> Any:
    dp = dp_axes(mesh)

    def one(path, leaf):
        if leaf.ndim >= 1:
            spec = P(dp, *([None] * (leaf.ndim - 1)))
        else:
            spec = P()
        return named(mesh, fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, batch)


def activation_constraint(mesh: Mesh, h: jax.Array, *,
                          seq_shard: bool = False) -> jax.Array:
    """Residual-stream constraint between blocks: batch over dp and,
    optionally, sequence-parallel over model."""
    dp = dp_axes(mesh)
    spec = P(dp, "model", None) if seq_shard else P(dp, None, None)
    return jax.lax.with_sharding_constraint(h, named(mesh, spec))


def logits_sharding(mesh: Mesh) -> NamedSharding:
    return named(mesh, P(dp_axes(mesh), None, "model"))


# --------------------------------------------- paged pool plane partitioning
# Rules for the ``DevicePoolPlanes`` dict under a serving mesh
# (axes ("data", "model")).  Pages shard over "data" — each data shard owns
# a contiguous page range matching its per-shard free list, so append and
# gather stay shard-local.  Dense HOT/COLD payloads additionally shard
# their kv-head dim over "model" (tensor-parallel heads in the fused
# kernel).  PACKED planes (sym/ofs/stored) CANNOT head-shard: the APack
# stream layout interleaves heads across lanes, so every model shard keeps
# the full compressed words for its pages and the kernel decodes the full
# page then slices its local head block.  Table planes (vm/ol/cum) are
# small and fully replicated.
_PLANE_RULES: dict[str, tuple] = {
    "tok_k": ("data", None, "model", None),
    "tok_v": ("data", None, "model", None),
    "cold_k": ("data", None, "model", None),
    "cold_v": ("data", None, "model", None),
    "tok_sk": ("data", None, "model"),
    "tok_sv": ("data", None, "model"),
    "pscale_k": ("data", "model"),
    "pscale_v": ("data", "model"),
    "sym_k": ("data", None, None),
    "sym_v": ("data", None, None),
    "ofs_k": ("data", None, None),
    "ofs_v": ("data", None, None),
    "stored_k": ("data", None),
    "stored_v": ("data", None),
    "vm": (None, None),
    "ol": (None, None),
    "cum": (None, None),
}


def plane_pspec(name: str) -> P:
    """``shard_map`` in/out PartitionSpec for one pool plane by name."""
    try:
        return P(*_PLANE_RULES[name])
    except KeyError:
        raise KeyError(f"no plane partition rule for {name!r}") from None


def plane_pspecs(planes: dict | None = None) -> dict:
    """PartitionSpec dict matching a ``DevicePoolPlanes.planes`` dict
    (or the full rule set when called without one — the planes dict key
    set is fixed per pool layout, so spec builders that run before any
    pool exists can use the rules directly)."""
    return {k: plane_pspec(k) for k in (_PLANE_RULES if planes is None
                                        else planes)}


# Packed-WEIGHT plane rules (modules.PackedWeight / CompressedLinear leaf
# order).  The weight stream layout is kt-major — stream index
# (kt*nn + j)*TILE_N + c — so a contiguous shard of the stream (last)
# axis is a contiguous K-tile range: sharding sym/ofs/stored over
# "model" K-splits the matmul and ``modules.packed_proj`` reassembles
# the row-parallel partials with a psum.  Dequant scale is per OUTPUT
# column and the matmul is linear in it, so it replicates exactly;
# table planes (v_min/ol/cum) are tiny and replicate.  Weight planes
# never shard over "data": every decode job reads every weight.
PACKED_LEAF_KINDS = ("sym", "ofs", "stored", "v_min", "ol", "cum", "scale")
_PACKED_SPLIT_KINDS = frozenset({"sym", "ofs", "stored"})


def packed_leaf_pspecs(leaves, *, splittable: bool) -> list[P]:
    """PartitionSpecs for one ``CompressedLinear``'s leaves, in flatten
    order (``PACKED_LEAF_KINDS``; a stacked layer axis, if present, just
    adds a leading replicated dim).  ``splittable=False`` (an
    indivisible K-tile count, or no model axis) degrades every leaf to
    replicated — same fall-back policy as ``fit_spec``."""
    specs = []
    for kind, leaf in zip(PACKED_LEAF_KINDS, leaves):
        if splittable and kind in _PACKED_SPLIT_KINDS:
            specs.append(P(*([None] * (leaf.ndim - 1)), "model"))
        else:
            specs.append(P())
    return specs


def plane_shardings(mesh: Mesh, planes: dict) -> dict:
    """NamedSharding dict for placing the pool planes on a serving mesh.

    ``fit_spec`` drops any axis that doesn't divide (e.g. kv-heads on an
    oversized model axis -> replicated heads; the kernel TP path is gated
    on divisibility separately)."""
    return {k: named(mesh, fit_spec(plane_pspec(k), v.shape, mesh))
            for k, v in planes.items()}


# ------------------------------------------------------- model-code context
# GSPMD propagation alone loses the batch sharding through scan carries
# (measured: full-global-batch fp32 logits per device).  Model code calls
# ``constrain(x, kind)``, a no-op unless the launcher installed a mesh.
_CTX: dict = {"mesh": None, "seq_shard": False, "moe_ep": False}


def set_mesh_context(mesh: Mesh | None, *, seq_shard: bool = False,
                     moe_ep: bool = False) -> None:
    _CTX["mesh"] = auto_mesh(mesh)
    _CTX["seq_shard"] = seq_shard
    _CTX["moe_ep"] = moe_ep


class mesh_context:
    def __init__(self, mesh: Mesh, *, seq_shard: bool = False,
                 moe_ep: bool = False):
        self.mesh, self.seq_shard, self.moe_ep = mesh, seq_shard, moe_ep

    def __enter__(self):
        self.prev = dict(_CTX)
        set_mesh_context(self.mesh, seq_shard=self.seq_shard,
                         moe_ep=self.moe_ep)

    def __exit__(self, *exc):
        _CTX.update(self.prev)


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """kind: 'residual' [B,S,D] | 'logits' [B,S,V] | 'batch_only'."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    dp = dp_axes(mesh)
    if kind == "residual":
        spec = P(dp, "model", None) if _CTX["seq_shard"] else P(dp, None, None)
    elif kind == "logits":
        spec = P(dp, None, "model")
    elif kind == "heads":          # [B, S, H, dh] — TP over heads
        spec = P(dp, None, "model", None)
    elif kind == "ffn_hidden":     # [B, S, F] — TP over the hidden dim
        spec = P(dp, None, "model")
    elif kind == "experts":        # [E, C, D] / [E, C, F] — EP over experts
        ax = dp if _CTX.get("moe_ep") else "model"
        spec = P(ax, *([None] * (x.ndim - 1)))
    elif kind == "kv_cache":       # [B, S, Hkv, dh]
        if x.shape[2] % _axes_size(mesh, "model") == 0:
            spec = P(dp, None, "model", None)
        else:                      # kv heads indivisible -> shard sequence
            spec = P(dp, "model", None, None)
    else:
        spec = P(dp, *([None] * (x.ndim - 1)))
    # Intermediates may shard unevenly (GSPMD pads) — crucial for e.g.
    # 24 heads on a 16-way model axis (measured: fit-dropping the head
    # sharding replicated the whole attention computation 16x).  Only the
    # batch dim is fit-checked: padding batch=1 across 32 DP shards would
    # waste, not help.
    if x.shape[0] % _axes_size(mesh, spec[0]) != 0:
        spec = P(None, *spec[1:])
    return jax.lax.with_sharding_constraint(x, named(mesh, spec))
