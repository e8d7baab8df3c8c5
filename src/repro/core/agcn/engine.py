"""Backend-dispatched AGCN execution engine (plan-compile-then-execute).

The paper's accelerator runs the reorganized graph+spatial dataflow, the
cavity-pruned temporal conv and the runtime RFC compress as one fused
on-chip pipeline.  This module is the software analogue: instead of the
model re-deriving gathers / packings / padded graphs on every step, an
``ExecutionPlan`` is compiled **once** from ``(params, PrunePlan,
ModelConfig)`` and the hot loop only executes it.

Two backends implement the per-block ops:

  reference — the pure-jnp einsum path (extracted from ``model.py``); fully
              traceable, so it also serves the differentiable train path.
  pallas    — the fused Pallas kernels in ``repro.kernels.ops``:
              ``graph_sconv`` (graph matmul + 1×1 conv in one VMEM pass),
              packed ``cavity_tconv`` (kept-tap matmuls only), and RFC
              encode/decode between blocks as the inter-layer activation
              format.  The plan derives interpret mode from the platform
              (``ops.interpret_mode``): interpreted on the CPU, compiled
              on a TPU — there is no option to choose it.

The plan is a registered pytree: its arrays are jit arguments (so two
plans built from the same config hit the same jit cache entry — no
re-tracing, and *no re-packing inside the jitted step*), while shapes,
strides and flags live in the hashable static aux.

Pallas plans must be built **outside** jit: cavity weight packing
(``ops.pack_cavity_weights``) is host-side numpy by design — that is the
"compile" in plan-compile-then-execute.

Besides clip mode (``execute``), every plan also runs **streaming**: per-
frame continual inference through ``step_frame`` against a ``StreamState``
of per-block temporal ring buffers — see the streaming section below and
tests/test_streaming.py for the clip-parity contract.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.core.agcn import adaptive
from repro.core.agcn.graph import GraphTopology, dense_to_csr, get_topology
from repro.core.pruning.plan import PrunePlan
from repro.core.quant import quantize_q88
from repro.distributed.sharding import shard_batch
from repro.kernels import ops

BACKENDS = ("reference", "pallas")

# why streaming (step_frame, the session slab, GcnService) refuses the
# published C_k
STREAMING_CK_REFUSAL = (
    "ck_form='clip' is the published C_k, pooled over the whole clip: a "
    "live stream has no whole clip to pool over, so streaming and session "
    "serving run ck_form='window' (the trailing-window form); run the "
    "published form on the clip path (engine.execute)")


# ---------------------------------------------------------------------------
# plan containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockStatic:
    """Hashable per-block metadata (shapes and flags the tracer must see
    as python constants)."""

    stride: int
    cin: int                 # full block-input width (pre kept_in gather)
    cout: int
    n_kept_filters: int
    tkernel: int
    use_ck: bool
    pruned_in: bool          # kept_in gather present
    pruned_filters: bool     # kept_filters scatter present
    sconv: str = "dense"     # spatial-conv path: "dense" | "csr"
    ck_form: str = "window"  # C_k form when use_ck ("window" | "clip")


@dataclasses.dataclass(frozen=True)
class PlanStatic:
    """Hashable whole-plan metadata — the jit-cache key of a compiled
    ExecutionPlan (backend selection, the platform-derived Pallas
    ``interpret`` mode, C5 input skip, RFC
    inter-layer format flags, streaming shape constants, and the per-block
    ``BlockStatic`` tuple)."""

    backend: str
    interpret: bool
    input_skip: int
    use_rfc: bool            # RFC roundtrip between blocks (pallas format)
    rfc_bank: int
    tkernel: int
    joints: int
    in_channels: int
    stream_pool: int         # streaming logit pool: 0 = cumulative (clip
                             # parity), W > 0 = sliding window of W frames
    blocks: Tuple[BlockStatic, ...]
    topology: str = "ntu25"  # skeleton this plan was compiled for
    valid_joints: int = 0    # topology's own V (<= joints when slab-padded;
                             # 0 = legacy plan, treated as == joints)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ExecutionPlan:
    """Compiled, engine-ready form of one AGCN stream.

    ``arrays`` is the pytree the jitted step consumes (pre-gathered /
    pre-quantized / pre-packed weights, precomputed graphs ``A + B_k``,
    kept-index vectors); ``static`` is the hashable aux.
    """

    arrays: Dict[str, Any]
    static: PlanStatic

    def tree_flatten(self):
        """Pytree split: arrays are jit leaves, PlanStatic is hashable aux."""
        return (self.arrays,), self.static

    @classmethod
    def tree_unflatten(cls, static, children):
        """Rebuild from (aux, leaves) — the jax pytree protocol inverse."""
        return cls(arrays=children[0], static=static)


# leaf offsets inside a packed buffer are multiples of this many elements,
# so every unpacked leaf starts on a whole tile of the flat buffer
PACK_ALIGN = 1024


@dataclasses.dataclass(frozen=True, eq=False)
class ConstantLayout:
    """Hashable layout of a pytree packed into one flat buffer per dtype —
    the jit-cache key of a :class:`PackedConstants`, as :class:`PlanStatic`
    is of an ExecutionPlan: the tree's structure (plan statics included)
    and each leaf's ``(buffer, offset, shape)`` in flattening order.  The
    hash is computed once: a jitted call hashes its arguments' aux data on
    every dispatch."""

    treedef: Any
    leaves: Tuple[Tuple[int, int, Tuple[int, ...]], ...]

    def __post_init__(self):
        object.__setattr__(self, "_hash", hash((self.treedef, self.leaves)))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return self is other or (
            isinstance(other, ConstantLayout) and self._hash == other._hash
            and (self.treedef, self.leaves) == (other.treedef, other.leaves))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedConstants:
    """A pytree of constant arrays (the serving tick's ExecutionPlans and
    frozen BN statistics) held as one flat device buffer per dtype.

    A jitted call pays host time for every array it takes, so the service
    passes its constants packed: ``buffers`` are the jit leaves, and
    :meth:`unpack` rebuilds the original tree with static slices and
    reshapes — inside the trace, where they cost no host time.  Packing
    keeps every leaf's dtype and bits."""

    buffers: Tuple[Any, ...]
    layout: ConstantLayout

    def tree_flatten(self):
        """Pytree split: the buffers are leaves, the layout is static aux."""
        return (self.buffers,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        """Rebuild from (aux, leaves) — the jax pytree protocol inverse."""
        return cls(buffers=tuple(children[0]), layout=layout)

    def unpack(self) -> Any:
        """The packed tree, leaf for leaf, bit for bit."""
        lay = self.layout
        return jax.tree_util.tree_unflatten(lay.treedef, [
            jax.lax.slice(self.buffers[b], (off,),
                          (off + int(np.prod(shape)),)).reshape(shape)
            for b, off, shape in lay.leaves])


def pack_constants(tree: Any) -> PackedConstants:
    """Pack ``tree``'s arrays into one flat device buffer per dtype, in
    order of dtype name, each leaf at an offset aligned to
    :data:`PACK_ALIGN` elements (host-side and once: the arrays are read
    back to assemble the buffers)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    host = [np.asarray(x) for x in leaves]
    kinds = {x.dtype.name: x.dtype for x in host}
    dtypes = tuple(sorted(kinds))
    ends = dict.fromkeys(dtypes, 0)
    table = []
    for x in host:
        d = x.dtype.name
        table.append((dtypes.index(d), ends[d], tuple(x.shape)))
        ends[d] += -(-x.size // PACK_ALIGN) * PACK_ALIGN
    bufs = [np.zeros(ends[d], kinds[d]) for d in dtypes]
    for x, (b, off, _) in zip(host, table):
        bufs[b][off:off + x.size] = x.ravel()
    return PackedConstants(
        buffers=tuple(jnp.asarray(b) for b in bufs),
        layout=ConstantLayout(treedef, tuple(table)))


# ---------------------------------------------------------------------------
# shared math (used by both backends and by the legacy-compatible paths)
# ---------------------------------------------------------------------------

def _bn_stats(x: jnp.ndarray, eps: float = 1e-5):
    """(mean, inv) over all-but-channel axes — the clip-mode batch stats."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes, keepdims=True)
    var = jnp.var(x, axes, keepdims=True)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
    return mean, inv


def _bn_norm(x, p, mean, inv):
    return (x - mean) * inv * p["scale"] + p["bias"]


def batch_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray],
               eps: float = 1e-5) -> jnp.ndarray:
    """Stateless batch norm: f32-accumulated stats, elementwise math in the
    activation dtype (see model.py docstring / EXPERIMENTS §Perf)."""
    mean, inv = _bn_stats(x, eps)
    return _bn_norm(x, p, mean, inv)


def _bn_live(site: str, x, p):
    """Default BN tap: clip-mode batch statistics, site ignored."""
    return batch_norm(x, p)


class _BNRecorder:
    """BN tap that captures each site's (mean, inv) while behaving exactly
    like the live tap — the calibration pass behind streaming's frozen
    statistics (per-frame BN cannot see clip-wide stats)."""

    def __init__(self):
        self.stats: Dict[str, Dict[str, jnp.ndarray]] = {}

    def __call__(self, site, x, p):
        mean, inv = _bn_stats(x)
        self.stats[site] = {"mean": mean.reshape(-1), "inv": inv.reshape(-1)}
        return _bn_norm(x, p, mean, inv)


class _BNFrozen:
    """BN tap applying previously recorded statistics (streaming hot path).
    Flat (C,) stats broadcast over any leading layout, so the same stats
    serve clip (N,T,V,C) and frame (N,V,C) shapes."""

    def __init__(self, stats: Dict[str, Dict[str, jnp.ndarray]]):
        self.stats = stats

    def __call__(self, site, x, p):
        s = self.stats[site]
        return _bn_norm(x, p, s["mean"], s["inv"])


def _proj(x, w, bnp, stride, bn=_bn_live, site=""):
    if stride != 1:
        x = x[:, ::stride]
    return bn(site, jnp.einsum("ntvc,co->ntvo", x, w), bnp)


def _scatter_filters(out: jnp.ndarray, fidx: jnp.ndarray, cout: int):
    """Scatter compacted filter outputs back to full width (pruned filters
    stay zero so the residual path sees the accelerator's shortcut layout)."""
    full = jnp.zeros((*out.shape[:-1], cout), out.dtype)
    return full.at[..., fidx].set(out)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

class Backend(Protocol):
    """Per-block op provider.  ``ba`` are the block's plan arrays, ``bs``
    its static metadata; activations are (N, T, V, C)."""

    name: str

    def spatial(self, x: jnp.ndarray, ba: Dict[str, Any],
                bs: BlockStatic,
                ck: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Graph spatial conv Σ_k (G_k·x)·W_k: (N,T,V,Cin) -> (N,T,V,Cout).
        ``ck`` optionally adds a precomputed data-dependent graph to G_k
        (repro.core.agcn.adaptive): per frame (N,T,V,V) to every subset
        in the windowed form, per sample and subset (N,K,V,V) in the
        published clip form."""
        ...

    def clip_ck(self, x: jnp.ndarray, ba: Dict[str, Any],
                valid_joints: int) -> jnp.ndarray:
        """The published whole-clip C_k of a block input with kept
        channels gathered: (N,T,V,C) -> (N,K,V,V), in the graph
        orientation ``G[out, in]``."""
        ...

    def temporal(self, x: jnp.ndarray, ba: Dict[str, Any],
                 bs: BlockStatic) -> jnp.ndarray:
        """Clip-mode temporal conv over T: (N,T,V,C) -> (N,T_out,V,Cout)."""
        ...

    def temporal_step(self, win: jnp.ndarray, ba: Dict[str, Any],
                      bs: BlockStatic) -> jnp.ndarray:
        """One output frame from a K-frame window: (N,K,V,C) -> (N,V,Cout)."""
        ...

    def transfer(self, h: jnp.ndarray, ps: PlanStatic) -> jnp.ndarray:
        """Inter-block activation transfer (identity / RFC roundtrip)."""
        ...


def _gather_in(x: jnp.ndarray, ba: Dict[str, Any]) -> jnp.ndarray:
    if ba["kept_in"] is not None:
        return jnp.take(x, ba["kept_in"], axis=-1)
    return x


def _spatial_einsum(x: jnp.ndarray, ba: Dict[str, Any],
                    bs: BlockStatic,
                    ck: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reference math for Σ_k (G_k·x)·W_k (+ optional data-dependent C_k).

    ``ck`` is a precomputed data-dependent graph (repro.core.agcn.
    adaptive) added to the static ``A_k + B_k``: the windowed form's
    per-frame (N, T, V, V) graph, added to every subset — the engine
    computes it (clip: per frame index; streaming: from the embedding
    rings) because the window state and the padded-joint masking live
    above the backend — or the published form's per-sample, per-subset
    (N, K, V, V) graph (``bs.ck_form == "clip"``).  A plan padded to a slab
    Vmax may be run on a clip at the topology's own joint count (BN
    calibration); the padded graph is zero outside its valid joints, so
    slicing it down to x's V is exact."""
    G = ba["G"].astype(x.dtype)
    if G.shape[-1] != x.shape[2]:
        G = G[:, : x.shape[2], : x.shape[2]]
    Wk = ba["Wk"].astype(x.dtype)
    if ck is not None and bs.ck_form == "clip":
        Gn = G[None] + ck.astype(x.dtype)                    # (N,K,V,V)
        return jnp.einsum("ntvc,nkwv,kco->ntwo", x, Gn, Wk)
    if ck is not None:
        Gn = G[None, None] + ck.astype(x.dtype)[:, :, None]  # (N,T,K,V,V)
        y = jnp.einsum("ntvc,ntkwv->ntkwc", x, Gn)
        return jnp.einsum("ntkwc,kco->ntwo", y, Wk)
    return jnp.einsum("ntvc,kwv,kco->ntwo", x, G, Wk)


def _spatial_csr_ref(x: jnp.ndarray, ba: Dict[str, Any],
                     bs: BlockStatic) -> jnp.ndarray:
    """Reference CSR spatial conv: gather-accumulate over the plan's
    indptr/indices.  The CSR is built at the topology's own V; when x runs
    wider (slab-padded frames) the extra output rows are zero-padded back —
    exact, because the graph never references padded joints."""
    from repro.kernels import ref as _ref

    N, T, V, C = x.shape
    Wk = ba["Wk"].astype(x.dtype)
    out = _ref.graph_sconv_csr_ref(
        x.reshape(N * T, V, C), ba["csr_indptr"], ba["csr_indices"],
        ba["csr_values"].astype(x.dtype), Wk)
    if out.shape[1] < V:
        out = jnp.pad(out, ((0, 0), (0, V - out.shape[1]), (0, 0)))
    return out.reshape(N, T, V, -1)


class ReferenceBackend:
    """Pure-jnp path — today's model math, executed from the plan."""

    name = "reference"

    def spatial(self, x, ba, bs, ck=None):
        """Kept-channel gather + the Σ_k (G_k·x)·W_k einsum (optional
        windowed C_k via ``ck``), or the CSR gather-accumulate when the
        plan chose ``sconv="csr"``."""
        xg = _gather_in(x, ba)
        if bs.sconv == "csr" and not bs.use_ck:
            return _spatial_csr_ref(xg, ba, bs)
        return _spatial_einsum(xg, ba, bs, ck=ck)

    def clip_ck(self, x, ba, valid_joints):
        """The published C_k in jnp (``adaptive.clip_ck``)."""
        return adaptive.clip_ck(x, ba["ck_w"], ba["ck_b"],
                                int(ba["Wk"].shape[0]), valid_joints)

    def temporal(self, x, ba, bs):
        """Dense masked temporal conv, 'same' padding, stride on T; pruned
        filters are scattered back to full width for the residual path."""
        w = ba["tw"].astype(x.dtype)                  # (F_kept, C, K) masked
        K = w.shape[-1]
        pad = K // 2
        rhs = jnp.transpose(w, (2, 1, 0))[:, None, :, :]   # (K, 1, C, F)
        out = jax.lax.conv_general_dilated(
            x, rhs,
            window_strides=(bs.stride, 1),
            padding=((pad, pad), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        out = out + ba["tb"]
        if bs.pruned_filters:
            out = _scatter_filters(out, ba["kept_filters"], bs.cout)
        return out

    def temporal_step(self, win, ba, bs):
        """One output frame from a chronological window (N, K, V, C) —
        the streaming form of ``temporal`` (stride is emission gating,
        handled by the engine; the window always yields one output)."""
        w = ba["tw"].astype(win.dtype)                # (F_kept, C, K)
        out = jnp.einsum("nkvc,fck->nvf", win, w) + ba["tb"]
        if bs.pruned_filters:
            out = _scatter_filters(out, ba["kept_filters"], bs.cout)
        return out

    def transfer(self, h, ps):
        """Identity — reference activations cross blocks uncompressed."""
        return h


class PallasBackend:
    """Fused Pallas kernels; RFC roundtrip is the inter-layer format.

    The data-dependent C_k graph cannot be precompiled (it is a function
    of the activations).  Published (clip-form) C_k blocks stay in the
    kernels: ``ops.clip_similarity`` computes each sample's graphs and
    the per-sample ``ops.graph_sconv_rows`` aggregates with
    ``A_k + B_k + C_k``.  Windowed C_k blocks apply their per-frame graph
    (built by streaming with the fused ``ops.windowed_similarity`` kernel
    over the embedding rings) through the reference einsum.  With C_k off
    the paper's deployment path, Table I, is unchanged.
    """

    name = "pallas"

    def __init__(self, interpret: bool):
        self.interpret = interpret

    def spatial(self, x, ba, bs, ck=None):
        """Fused graph+1×1 kernel (``ops.graph_sconv``) on the padded
        (K, Vp, Vp) plan graph, or the ELL gather kernel when the plan
        chose ``sconv="csr"``; published C_k blocks run the per-sample
        kernel (``ops.graph_sconv_rows``) on ``Gp + ck``, windowed C_k
        blocks apply the precomputed ``ck`` through the einsum."""
        xg = _gather_in(x, ba)
        if bs.use_ck and bs.ck_form == "clip":
            gp = ba["Gp"]
            pad = gp.shape[-1] - ck.shape[-1]
            g = gp[None] + jnp.pad(ck.astype(gp.dtype),
                                   ((0, 0), (0, 0), (0, pad), (0, pad)))
            return shard_batch(
                lambda vg, w: ops.graph_sconv_rows(
                    *vg, w, interpret=self.interpret), (xg, g), ba["Wk"])
        if bs.use_ck:
            return _spatial_einsum(xg, ba, bs, ck=ck)
        if bs.sconv == "csr":
            return shard_batch(
                lambda v, i, e, w: ops.graph_sconv_csr(
                    v, i, e, w, interpret=self.interpret),
                xg, ba["ell_idx"], ba["ell_val"], ba["Wk"])
        return shard_batch(
            lambda v, g, w: ops.graph_sconv(v, g, w,
                                            interpret=self.interpret),
            xg, ba["Gp"], ba["Wk"])

    def clip_ck(self, x, ba, valid_joints):
        """The published C_k through the ``ck_proj`` and ``ck_sim``
        kernels (``ops.clip_similarity``)."""
        kv = int(ba["Wk"].shape[0])
        return shard_batch(
            lambda v, w, b: ops.clip_similarity(
                v, w, b, kv, valid_joints, interpret=self.interpret),
            x, ba["ck_w"], ba["ck_b"])

    def temporal(self, x, ba, bs):
        """Packed cavity tconv kernel over the flattened (N·V, T, C) rows —
        only the kept taps are issued (the paper's C2 FLOP skip)."""
        def conv(x, wp, taps, inv):
            N, T, V, C = x.shape
            xb = jnp.transpose(x, (0, 2, 1, 3)).reshape(N * V, T, C)
            out = ops.cavity_tconv(
                xb, wp, taps, inv, num_filters=bs.n_kept_filters,
                kernel_size=bs.tkernel, stride=bs.stride,
                interpret=self.interpret,
            )                                        # (N*V, T_out, F_kept)
            return jnp.transpose(
                out.reshape(N, V, out.shape[1], -1), (0, 2, 1, 3))

        out = shard_batch(conv, x, ba["wp"], ba["taps"], ba["inv_perm"])
        out = out + ba["tb"]
        if bs.pruned_filters:
            out = _scatter_filters(out, ba["kept_filters"], bs.cout)
        return out

    def temporal_step(self, win, ba, bs):
        """Single-timestep packed cavity tconv on a chronological window
        (N, K, V, C) — the same packed weights/taps, T_pad == K."""
        def conv(win, wp, taps, inv):
            N, K, V, C = win.shape
            xb = jnp.transpose(win, (0, 2, 1, 3)).reshape(N * V, K, C)
            out = ops.cavity_tconv_step(
                xb, wp, taps, inv, num_filters=bs.n_kept_filters,
                interpret=self.interpret,
            )                                         # (N*V, F_kept)
            return out.reshape(N, V, -1)

        out = shard_batch(conv, win, ba["wp"], ba["taps"], ba["inv_perm"])
        out = out + ba["tb"]
        if bs.pruned_filters:
            out = _scatter_filters(out, ba["kept_filters"], bs.cout)
        return out

    def transfer(self, h, ps):
        """RFC encode/decode roundtrip — the compressed inter-layer
        activation format (lossless on post-ReLU values)."""
        if not ps.use_rfc:
            return h
        return _rfc_decode(_rfc_encode(h, ps), ps)


def _rfc_encode(h: jnp.ndarray, ps: PlanStatic):
    """RFC-encode an inter-block activation with the plan's bank width:
    ``(values, hot)``, one kernel call per device shard of the slot axis."""
    return shard_batch(
        lambda v: ops.rfc_encode(v, bank=ps.rfc_bank,
                                 interpret=ps.interpret), h)


def _rfc_decode(encoded, ps: PlanStatic) -> jnp.ndarray:
    """Inverse of :func:`_rfc_encode` on its ``(values, hot)`` pair."""
    return shard_batch(
        lambda vh: ops.rfc_decode(*vh, bank=ps.rfc_bank,
                                  interpret=ps.interpret), tuple(encoded))


def get_backend(name: str, interpret: bool) -> Backend:
    """Backend registry lookup: ``reference`` | ``pallas`` (cheap to call
    inside traced code — backends are stateless op providers).
    ``interpret`` is the plan's platform-derived Pallas mode
    (``PlanStatic.interpret``); the reference backend ignores it."""
    if name == "reference":
        return ReferenceBackend()
    if name == "pallas":
        return PallasBackend(interpret=interpret)
    raise ValueError(f"unknown backend {name!r} (expected one of {BACKENDS})")


# ---------------------------------------------------------------------------
# plan compilation
# ---------------------------------------------------------------------------

def _to_numpy(x) -> np.ndarray:
    """Concretize for host-side packing — raises a clear error if a pallas
    plan is being built inside jit (packing must happen outside the step)."""
    try:
        return np.asarray(x)
    except jax.errors.TracerArrayConversionError as e:
        raise ValueError(
            "pallas ExecutionPlans must be built outside jit: cavity weight "
            "packing is host-side (plan-compile-then-execute)") from e


def _graph_density(g, eps: float) -> Optional[float]:
    """Fraction of |entries| > eps, or None when ``g`` is a tracer (plan
    build inside jit — the train path — cannot measure density)."""
    try:
        gn = np.asarray(g)
    except jax.errors.TracerArrayConversionError:
        return None
    return float((np.abs(gn) > eps).mean())


def build_execution_plan(
    params: Dict[str, Any],
    cfg: ModelConfig,
    prune_plan: Optional[PrunePlan] = None,
    *,
    quant: bool = False,
    backend: str = "reference",
    use_rfc: Optional[bool] = None,
    topology: Optional[Any] = None,
    pad_joints: Optional[int] = None,
    sconv: str = "auto",
    csr_eps: float = 0.0,
    csr_density: float = 0.5,
) -> ExecutionPlan:
    """Compile ``(params, PrunePlan, ModelConfig)`` into an ExecutionPlan.

    Everything the hot loop should not redo per step happens here: kept-
    channel index gathers, graph precompute ``A + B_k`` (padded to
    ``(K, Vp, Vp)`` for the pallas kernel), temporal filter gather + cavity
    tap masking, cavity weight packing, Q8.8 weight quantization, and the
    per-block shape bookkeeping.  Building is pure: same inputs produce an
    identical plan (leaf-for-leaf), so jitted steps taking the plan as an
    argument never retrace across rebuilds.

    Variable topology: ``topology`` names a registry skeleton (or passes a
    :class:`~repro.core.agcn.graph.GraphTopology` directly; default
    ``ntu25``) and ``pad_joints`` pads every joint-indexed plan array to a
    wider slab width Vmax so plans for different skeletons share one slab —
    padded rows/cols are zero, so the math at the topology's own joints is
    unchanged.  ``sconv`` picks the per-block spatial-conv path: ``dense``
    (padded matmul), ``csr`` (gather-accumulate over the measured nonzero
    entries of ``A + B_k``), or ``auto`` — CSR when the fraction of
    ``|G| > csr_eps`` entries is at most ``csr_density``, dense otherwise
    (with zero ``csr_eps`` the learned dense B_k keeps every graph at
    density 1.0, so auto picks dense — today's path — until B_k is
    thresholded).

    Pallas plans record ``interpret`` from the platform
    (:func:`repro.kernels.ops.interpret_mode`): the CPU interprets the
    kernels, a TPU compiles them, and any other platform raises.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    if sconv not in ("auto", "dense", "csr"):
        raise ValueError(f"unknown sconv mode {sconv!r}")
    from repro.core.agcn.model import AGCN_STRIDES  # no import cycle: model
    strides = cfg.gcn_strides or AGCN_STRIDES       # lazily imports engine
    if isinstance(topology, GraphTopology):
        topo = topology
    else:
        topo = get_topology(topology or "ntu25", cfg.gcn_kv)
    vj = topo.num_joints                            # topology's own V
    V = int(pad_joints) if pad_joints is not None else vj
    if V < vj:
        raise ValueError(
            f"pad_joints={V} is narrower than topology {topo.name!r} "
            f"(V={vj})")
    Vp = ((V + 7) // 8) * 8
    # host-side numpy graph build — stays concrete even under a jit trace
    # (the reference backend's plan build is traced by the train path)
    A = topo.adjacency.astype(np.float32)

    blocks_a: List[Dict[str, Any]] = []
    blocks_s: List[BlockStatic] = []
    for b, blk in enumerate(params["blocks"]):
        pb = prune_plan.blocks[b] if prune_plan is not None else None
        cout = int(blk["tconv_w"].shape[0])
        cin_full = int(blk["Wk"].shape[1])            # pre-gather block input
        use_ck = bool(cfg.use_ck and "theta" in blk)
        ck_form = cfg.ck_form if use_ck else "window"

        # --- spatial: graph precompute + kept-channel gather + quant ------
        if int(blk["Bk"].shape[-1]) != vj:
            raise ValueError(
                f"block {b}: learned graph B_k is "
                f"{tuple(blk['Bk'].shape)} but topology {topo.name!r} has "
                f"V={vj} joints — params were built for a different "
                f"topology")
        Gv = jnp.asarray(A, jnp.float32) + blk["Bk"].astype(jnp.float32)
        if V != vj:     # pad to the slab width; padded joints stay isolated
            G = jnp.zeros((Gv.shape[0], V, V),
                          jnp.float32).at[:, :vj, :vj].set(Gv)
        else:
            G = Gv
        Wk = blk["Wk"]
        if quant:
            Wk = quantize_q88(Wk)
        theta, phi = blk.get("theta"), blk.get("phi")
        kept_in = None
        if pb is not None:
            kept_in = jnp.asarray(pb.kept_in, jnp.int32)
            Wk = jnp.take(Wk, kept_in, axis=1)
            if use_ck:
                theta = jnp.take(theta, kept_in, axis=-2)
                phi = jnp.take(phi, kept_in, axis=-2)
        ck_w = ck_b = None
        if ck_form == "clip":
            # the published per-subset θ_k/φ_k as one (C, 2·K·Ce)
            # projection, θ_0 … θ_{K-1} then φ_0 … φ_{K-1}
            if theta.ndim != 3 or "theta_b" not in blk:
                raise ValueError(
                    f"block {b}: ck_form='clip' needs per-subset θ_k/φ_k "
                    f"(K, C, Ce) with biases; the params carry "
                    f"{tuple(theta.shape)} (the windowed form's)")
            ck_w = jnp.concatenate(
                [jnp.transpose(m, (1, 0, 2)).reshape(m.shape[1], -1)
                 for m in (theta, phi)], axis=-1)
            ck_b = jnp.concatenate([blk["theta_b"].reshape(-1),
                                    blk["phi_b"].reshape(-1)])
            theta = phi = None

        # --- temporal: filter gather + cavity mask + quant ----------------
        tw = blk["tconv_w"]                           # (F, C, K)
        if quant:
            tw = quantize_q88(tw)
        tb = blk["tconv_b"]
        kept_filters = None
        tap_mask = np.ones((cout, cfg.gcn_tkernel), bool)
        if pb is not None:
            kept_filters = jnp.asarray(pb.kept_filters, jnp.int32)
            tw = jnp.take(tw, kept_filters, axis=0)
            tb = jnp.take(tb, kept_filters)
            tap_mask = np.asarray(pb.tap_mask, bool)
            tw = tw * jnp.asarray(tap_mask, tw.dtype)[:, None, :]
        n_kept = int(tw.shape[0])

        # --- spatial path selection: dense padded vs CSR ------------------
        # a C_k block's graph A + B_k + C_k is data-dependent and dense
        # (a softmax has no zeros), so it always takes the dense path
        block_sconv = "dense"
        if sconv != "dense" and not use_ck:
            density = _graph_density(Gv, csr_eps)
            if sconv == "csr":
                if density is None:
                    raise ValueError(
                        "sconv='csr' plans must be built outside jit: CSR "
                        "packing is host-side (plan-compile-then-execute)")
                block_sconv = "csr"
            elif density is not None and density <= csr_density:
                block_sconv = "csr"

        ba: Dict[str, Any] = {
            "G": G, "Wk": Wk, "kept_in": kept_in,
            "theta": theta, "phi": phi, "ck_w": ck_w, "ck_b": ck_b,
            "bn_s": blk["bn_s"], "bn_t": blk["bn_t"],
            "tw": tw, "tb": tb, "kept_filters": kept_filters,
            "down_w": blk.get("down_w"), "bn_down": blk.get("bn_down"),
            "short_w": blk.get("short_w"), "bn_short": blk.get("bn_short"),
            "Gp": None, "wp": None, "taps": None, "inv_perm": None,
            "csr_indptr": None, "csr_indices": None, "csr_values": None,
            "ell_idx": None, "ell_val": None,
        }

        if block_sconv == "csr":
            # entries with |G| <= csr_eps (the dense B_k noise floor when
            # eps > 0) are dropped — that is the CSR/dense parity budget
            indptr, indices, values = dense_to_csr(np.asarray(Gv), csr_eps)
            if backend == "pallas":
                ei, ev = ops.pack_csr_ell(indptr, indices, values, Vp)
                ba["ell_idx"] = jnp.asarray(ei)
                ba["ell_val"] = jnp.asarray(ev)
            else:
                ba["csr_indptr"] = jnp.asarray(indptr)
                ba["csr_indices"] = jnp.asarray(indices)
                ba["csr_values"] = jnp.asarray(values)
            ba["G"] = None          # the CSR paths never read the dense form

        if backend == "pallas":
            if block_sconv == "dense":
                # padded graph (K, Vp, Vp): the kernel's sublane-aligned
                # layout
                Gp = jnp.zeros((G.shape[0], Vp, Vp), G.dtype)
                ba["Gp"] = Gp.at[:, :V, :V].set(G)
            # host-side cavity packing — dense blocks pack the full 9 taps
            wp, taps, inv = ops.pack_cavity_weights(
                _to_numpy(tw), tap_mask[:n_kept] if pb is not None
                else np.ones((n_kept, cfg.gcn_tkernel), bool))
            ba["wp"] = jnp.asarray(wp)
            ba["taps"] = jnp.asarray(taps)
            ba["inv_perm"] = jnp.asarray(inv, jnp.int32)
            # drop the dense forms the pallas path never reads — they'd ride
            # every jit call as dead payload (G stays only for the windowed
            # C_k blocks, which run the reference einsum)
            ba["tw"] = None
            if not use_ck or ck_form == "clip":
                ba["G"] = None

        blocks_a.append(ba)
        blocks_s.append(BlockStatic(
            stride=int(strides[b]), cin=cin_full, cout=cout,
            n_kept_filters=n_kept,
            tkernel=int(cfg.gcn_tkernel), use_ck=use_ck,
            pruned_in=kept_in is not None,
            pruned_filters=kept_filters is not None,
            sconv=block_sconv, ck_form=ck_form,
        ))

    input_skip = (prune_plan.input_skip if prune_plan is not None
                  else cfg.input_skip)
    if use_rfc is None:
        use_rfc = backend == "pallas"
    static = PlanStatic(
        backend=backend,
        interpret=backend == "pallas" and ops.interpret_mode(),
        input_skip=int(input_skip), use_rfc=bool(use_rfc),
        rfc_bank=int(cfg.rfc_bank), tkernel=int(cfg.gcn_tkernel),
        joints=int(V), in_channels=int(cfg.gcn_in_channels),
        stream_pool=int(cfg.gcn_stream_pool),
        blocks=tuple(blocks_s),
        topology=topo.name, valid_joints=int(vj),
    )
    data_bn = params["data_bn"]
    C = int(cfg.gcn_in_channels)
    if V != vj:
        # joint-major (V*C) flattened stem BN: pad scale->1 / bias->0 so the
        # padded joints pass through as identity (they are masked anyway)
        pad = (V - vj) * C
        data_bn = {
            "scale": jnp.concatenate(
                [data_bn["scale"], jnp.ones((pad,), data_bn["scale"].dtype)]),
            "bias": jnp.concatenate(
                [data_bn["bias"], jnp.zeros((pad,), data_bn["bias"].dtype)]),
        }
    # parent map (slab width, pad rows self-parent) — the bone-stream gather
    parents = np.arange(V, dtype=np.int32)
    parents[:vj] = topo.parents
    arrays = {
        "data_bn": data_bn,
        "blocks": blocks_a,
        "fc_w": params["fc_w"], "fc_b": params["fc_b"],
        "parents": jnp.asarray(parents),
    }
    return ExecutionPlan(arrays=arrays, static=static)


# ---------------------------------------------------------------------------
# execution (clip mode)
# ---------------------------------------------------------------------------

def _slice_data_bn(p: Dict[str, jnp.ndarray], width: int):
    """Match the joint-major (V*C) stem BN params to a narrower clip: a
    slab-padded plan calibrates at the topology's own V, and the padding
    tail (scale 1 / bias 0) carries no information."""
    if p["scale"].shape[0] == width:
        return p
    return {k: v[:width] for k, v in p.items()}


def _stem(arrays, x, input_skip: int, bn=_bn_live) -> jnp.ndarray:
    x = x.astype(arrays["data_bn"]["scale"].dtype)
    if input_skip > 1:
        x = x[:, ::input_skip]            # C5 input-skipping (frame sampling)
    N, T, V, C = x.shape
    h = x.reshape(N, T, V * C)
    p = _slice_data_bn(arrays["data_bn"], V * C)
    return bn("data_bn", h, p).reshape(N, T, V, C)


def _run_block(h, ba, bs, backend: Backend, bn=_bn_live, tag: str = "",
               vj: int = 0):
    ck = None
    if bs.use_ck and bs.ck_form == "clip":
        # the published C_k: one graph per sample and subset, pooled over
        # the whole clip
        ck = backend.clip_ck(_gather_in(h, ba),
                             ba, vj if 0 < vj < h.shape[2] else 0)
    elif bs.use_ck:
        # clip-mode windowed C_k: the same trailing-K recurrence the
        # streaming embedding rings evaluate, per frame index — which is
        # what makes streaming-vs-clip C_k parity a testable invariant
        ck = adaptive.clip_windowed_ck(
            _gather_in(h, ba), ba["theta"], ba["phi"], bs.tkernel,
            valid_joints=vj if 0 < vj < h.shape[2] else 0)
    s = backend.spatial(h, ba, bs, ck=ck)
    s = bn(tag + "bn_s", s, ba["bn_s"])
    down = (_proj(h, ba["down_w"], ba["bn_down"], 1, bn, tag + "bn_down")
            if ba["down_w"] is not None else h)
    s = jax.nn.relu(s + down)
    t = backend.temporal(s, ba, bs)
    t = bn(tag + "bn_t", t, ba["bn_t"])
    if ba["short_w"] is not None:
        res = _proj(h, ba["short_w"], ba["bn_short"], bs.stride, bn,
                    tag + "bn_short")
    else:
        res = h if bs.stride == 1 else h[:, ::bs.stride]
    return jax.nn.relu(t + res)


def block_outputs(plan: ExecutionPlan, x: jnp.ndarray) -> List[jnp.ndarray]:
    """Per-block post-ReLU activations (drives the sparsity probe)."""
    backend = get_backend(plan.static.backend, plan.static.interpret)
    h = _stem(plan.arrays, x, plan.static.input_skip)
    outs = []
    nblocks = len(plan.static.blocks)
    for b, (ba, bs) in enumerate(zip(plan.arrays["blocks"],
                                     plan.static.blocks)):
        h = _run_block(h, ba, bs, backend, vj=plan.static.valid_joints)
        outs.append(h)
        if b < nblocks - 1:
            h = backend.transfer(h, plan.static)
    return outs


def _forward(plan: ExecutionPlan, x: jnp.ndarray, bn) -> jnp.ndarray:
    backend = get_backend(plan.static.backend, plan.static.interpret)
    h = _stem(plan.arrays, x, plan.static.input_skip, bn)
    nblocks = len(plan.static.blocks)
    for b, (ba, bs) in enumerate(zip(plan.arrays["blocks"],
                                     plan.static.blocks)):
        h = _run_block(h, ba, bs, backend, bn, tag=f"b{b}/",
                       vj=plan.static.valid_joints)
        if b < nblocks - 1:
            h = backend.transfer(h, plan.static)
    pooled = h.mean(axis=(1, 2))                       # (N, C_last)
    return pooled @ plan.arrays["fc_w"] + plan.arrays["fc_b"]


def execute(plan: ExecutionPlan, x: jnp.ndarray) -> jnp.ndarray:
    """Run the compiled plan on a clip batch (N, T, V, C) -> logits."""
    return _forward(plan, x, _bn_live)


def collect_bn_stats(plan: ExecutionPlan, x: jnp.ndarray
                     ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Run one clip batch through the plan's own backend, recording every
    batch-norm site's (mean, inv) — the frozen statistics that let the
    streaming path reproduce clip logits (per-frame BN cannot see clip-wide
    stats).  Call outside jit: the recorder mutates a host-side dict."""
    rec = _BNRecorder()
    _forward(plan, x, rec)
    return rec.stats


# ---------------------------------------------------------------------------
# execution (streaming mode) — per-frame continual inference
# ---------------------------------------------------------------------------
#
# The same compiled plan runs frame-by-frame with stateful temporal rings:
# each block holds the last K(=tkernel) spatial outputs (its tconv input)
# plus the last K block inputs (residual source), and emits one output
# whenever the just-arrived frame completes a clip-mode window — every
# ``stride``-th input, ``pad = K//2`` frames behind real time (the clip
# conv's 'same' padding becomes a per-block latency).  Invalid frames
# (input-skip gaps, post-clip flush) write *zeros* into the tconv ring,
# which is exactly the clip conv's zero padding, so post-drain streaming
# logits equal clip logits (tests/test_streaming.py).  RFC encode/decode is
# applied to every emitted inter-block frame (pallas), and the running
# encoded activations live in the state.
#
# All per-stream clocks are tracked **per slot** (leading axis of every
# state leaf): slot s has its own raw-frame counter, per-block input
# counters, validity rings and logit pool.  A StreamState is therefore
# simultaneously one lockstep batch (every slot fed the same clip — the
# PR-2 streaming mode) and a **session slab**: independent live sessions
# occupying slots, admitted/evicted at different times by a host-side
# scheduler (repro.serving) through :func:`reset_slots`,
# :func:`step_frames` and the preemption pair :func:`snapshot_slots` /
# :func:`restore_slots`.  Free/dead slots are masked with ``valid=False``
# frames through the existing clip-validity machinery, so one compiled
# step serves any slot occupancy without retracing.

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StreamState:
    """Pytree state of S concurrent AGCN stream slots (the session slab).

    ``blocks[b]``: ring_s (S, K, V, cout) tconv-input ring, ring_h
    (S, K, V, cin) residual-source ring, valid (S, K) clip-validity bits,
    t (S,) int32 inputs seen at this block's time scale (per slot — slots
    admitted at different times run at different ring phases); ``use_ck``
    blocks additionally carry ck_th / ck_ph (S, K, V, Ce) windowed-C_k
    embedding rings (repro.core.agcn.adaptive) — per-slot leaves like any
    other, so snapshots, the fused tick's ring, and elastic/cross-replica
    migration carry them for free.  ``t_raw``
    (S,) counts raw frames per slot; ``pool_*`` hold the per-slot running
    temporal logit pool; ``bn_stats`` the frozen calibration (shared by all
    slots — calibrated once per plan, untouched by slot resets; empty in a
    bare state, whose steps take it as an override); ``rfc``
    the per-slot running RFC-encoded inter-block activations (pallas)."""

    t_raw: Any
    blocks: List[Dict[str, Any]]
    pool_ring: Any
    pool_sum: Any
    pool_t: Any
    bn_stats: Dict[str, Dict[str, Any]]
    rfc: Optional[List[Dict[str, Any]]]

    def tree_flatten(self):
        """Pytree split: every field is a leaf subtree (no static aux), so
        states ride jit boundaries and rebuilt states never retrace."""
        return ((self.t_raw, self.blocks, self.pool_ring, self.pool_sum,
                 self.pool_t, self.bn_stats, self.rfc), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from pytree children (field order of the dataclass)."""
        return cls(*children)


def _pad_data_bn_stats(bn_stats: Dict[str, Dict[str, Any]],
                       ps: PlanStatic) -> Dict[str, Dict[str, Any]]:
    """Pad the stem BN statistics of a topology-V calibration to the slab
    width (mean 0 / inv 1 — identity on the masked padded joints).  All
    other sites are per-channel (C,) and joint-count independent."""
    want = ps.joints * ps.in_channels
    db = bn_stats.get("data_bn")
    if db is None or db["mean"].shape[0] == want:
        return bn_stats
    pad = want - db["mean"].shape[0]
    out = dict(bn_stats)
    out["data_bn"] = {
        "mean": jnp.concatenate(
            [db["mean"], jnp.zeros((pad,), db["mean"].dtype)]),
        "inv": jnp.concatenate(
            [db["inv"], jnp.ones((pad,), db["inv"].dtype)]),
    }
    return out


def init_stream_state(
    plan: ExecutionPlan,
    batch: int,
    *,
    x_calib: Optional[jnp.ndarray] = None,
    bn_stats: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
    dtype=jnp.float32,
) -> StreamState:
    """Fresh zeroed StreamState for ``batch`` concurrent stream slots.

    Streaming needs frozen batch-norm statistics: pass ``x_calib`` (a
    representative clip batch — the stats are recorded from one clip-mode
    pass of this plan's own backend) or precomputed ``bn_stats`` from
    :func:`collect_bn_stats`.  The statistics are plan-level (shared by
    every slot), so one calibration serves sessions admitted at any later
    time.  ``bn_stats={}`` makes a *bare* state, per-slot leaves only, for
    a caller that passes the statistics to every step itself (the
    ``bn_stats`` override of :func:`step_frame`)."""
    ps = plan.static
    if any(bs.use_ck and bs.ck_form == "clip" for bs in ps.blocks):
        raise ValueError(STREAMING_CK_REFUSAL)
    if bn_stats is None:
        if x_calib is None:
            raise ValueError(
                "streaming needs frozen BN statistics: pass x_calib (a "
                "representative clip batch) or bn_stats from "
                "collect_bn_stats()")
        bn_stats = collect_bn_stats(plan, x_calib)
    bn_stats = _pad_data_bn_stats(bn_stats, ps)
    K, V = ps.tkernel, ps.joints
    blocks = []
    for b, bs in enumerate(ps.blocks):
        d = {
            "ring_s": jnp.zeros((batch, K, V, bs.cout), dtype),
            "ring_h": jnp.zeros((batch, K, V, bs.cin), dtype),
            "valid": jnp.zeros((batch, K), bool),
            "t": jnp.zeros((batch,), jnp.int32),
        }
        if bs.use_ck:
            # windowed-C_k embedding rings (repro.core.agcn.adaptive):
            # zero rows stand in for the pre-history window frames, so a
            # fresh slot's first windows match clip mode's leading edge.
            # Present only on use_ck plans — a C_k-off slab's state tree
            # (and therefore its snapshots, rings and golden digests) is
            # unchanged.
            ce = int(plan.arrays["blocks"][b]["theta"].shape[-1])
            d["ck_th"] = jnp.zeros((batch, K, V, ce), dtype)
            d["ck_ph"] = jnp.zeros((batch, K, V, ce), dtype)
        blocks.append(d)
    c_last = ps.blocks[-1].cout
    rfc = None
    if ps.use_rfc:
        rfc = [{"vals": jnp.zeros((batch, V, bs.cout), dtype),
                "hot": jnp.zeros((batch, V, bs.cout), dtype)}
               for bs in ps.blocks[:-1]]
    pool_ring = (jnp.zeros((batch, ps.stream_pool, c_last), dtype)
                 if ps.stream_pool > 0 else None)
    return StreamState(
        t_raw=jnp.zeros((batch,), jnp.int32), blocks=blocks,
        pool_ring=pool_ring, pool_sum=jnp.zeros((batch, c_last), dtype),
        pool_t=jnp.zeros((batch,), jnp.int32), bn_stats=bn_stats, rfc=rfc)


def init_session_slab(
    plan: ExecutionPlan,
    slots: int,
    *,
    x_calib: Optional[jnp.ndarray] = None,
    bn_stats: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
    dtype=jnp.float32,
) -> StreamState:
    """A fixed-capacity session slab: ``slots`` independent stream slots.

    Identical to :func:`init_stream_state` — a slab *is* a StreamState
    whose leading axis is slot capacity S rather than a lockstep batch.
    Named separately so serving code reads as what it means; the host-side
    admission/eviction scheduler lives in ``repro.serving``."""
    return init_stream_state(plan, slots, x_calib=x_calib,
                             bn_stats=bn_stats, dtype=dtype)


def _select_slots(keep_old, old: StreamState, new: StreamState) -> StreamState:
    """Per-slot select between two StreamStates: slots where ``keep_old`` is
    True keep ``old``'s per-slot leaves, all others take ``new``'s — the
    traced masking behind :func:`step_frames`'s ``hold``.  The shared
    plan-level ``bn_stats`` are taken from ``new`` (they are identical in
    both states by construction)."""
    keep_old = jnp.asarray(keep_old, bool)

    def sel(o, n):
        m = keep_old.reshape(keep_old.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, o, n)

    blocks = [{k: sel(ob[k], nb[k]) for k in nb}
              for ob, nb in zip(old.blocks, new.blocks)]
    rfc = None
    if new.rfc is not None:
        rfc = [{k: sel(orr[k], nr[k]) for k in nr}
               for orr, nr in zip(old.rfc, new.rfc)]
    return StreamState(
        t_raw=sel(old.t_raw, new.t_raw), blocks=blocks,
        pool_ring=(sel(old.pool_ring, new.pool_ring)
                   if new.pool_ring is not None else None),
        pool_sum=sel(old.pool_sum, new.pool_sum),
        pool_t=sel(old.pool_t, new.pool_t),
        bn_stats=new.bn_stats, rfc=rfc)


def reset_slots(state: StreamState, free) -> StreamState:
    """Zero the per-slot streaming state of every slot where ``free`` is
    True — the traced admission reset.

    ``free`` is a (S,) bool mask.  All per-slot leaves (rings, validity
    bits, block clocks, logit pools, RFC carries, raw-frame counters) are
    zeroed via ``jnp.where``, so admitting a new session into a recycled
    slot is one masked select inside the already-compiled step — never a
    retrace, never a state rebuild.  The shared frozen BN statistics are
    plan-level calibration and are left untouched."""
    free = jnp.asarray(free, bool)

    def z(leaf):
        m = free.reshape(free.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    blocks = [{k: z(v) for k, v in b.items()} for b in state.blocks]
    rfc = ([{k: z(v) for k, v in r.items()} for r in state.rfc]
           if state.rfc is not None else None)
    return StreamState(
        t_raw=z(state.t_raw), blocks=blocks,
        pool_ring=z(state.pool_ring) if state.pool_ring is not None else None,
        pool_sum=z(state.pool_sum), pool_t=z(state.pool_t),
        bn_stats=state.bn_stats, rfc=rfc)


def snapshot_slots(state: StreamState, idx) -> Dict[str, Any]:
    """Gather slot ``idx``'s per-slot streaming state out of the slab — the
    preemption capture.

    ``idx`` is a scalar (one slot) or an (k,) int vector (k slots); pass it
    as a traced array so every preemption reuses one jitted gather, never a
    retrace.  The snapshot covers **every** per-slot leaf of the
    :class:`StreamState` pytree — rings, validity bits, block clocks, logit
    pools, RFC carries, the raw-frame counter — and deliberately excludes
    ``bn_stats``: the frozen calibration is plan-level, shared by all slots,
    and travels with the plan rather than the session.  The returned dict
    is itself a pytree, so it rides jit boundaries and host round-trips.

    The locked invariant (tests/test_sessions.py, both backends):
    snapshot -> evict -> arbitrary foreign traffic in the slot ->
    :func:`restore_slots` -> resume produces logits identical (<=1e-3) to
    the uninterrupted session."""
    idx = jnp.asarray(idx, jnp.int32)

    def g(leaf):
        return jnp.take(leaf, idx, axis=0)

    return {
        "t_raw": g(state.t_raw),
        "blocks": [{k: g(v) for k, v in b.items()} for b in state.blocks],
        "pool_ring": (g(state.pool_ring)
                      if state.pool_ring is not None else None),
        "pool_sum": g(state.pool_sum),
        "pool_t": g(state.pool_t),
        "rfc": ([{k: g(v) for k, v in r.items()} for r in state.rfc]
                if state.rfc is not None else None),
    }


def restore_slots(state: StreamState, idx, snap: Dict[str, Any]
                  ) -> StreamState:
    """Scatter a :func:`snapshot_slots` capture back into slot ``idx`` — the
    preemption restore.

    The inverse of the snapshot gather: every per-slot leaf of ``snap`` is
    written into row ``idx`` of the corresponding slab leaf (one traced
    scatter when ``idx`` rides as an array — never a retrace), all other
    slots are untouched, and the shared frozen BN statistics stay the
    plan-level calibration of ``state``.  After the restore the slot resumes
    exactly where the snapshot left it: same ring phases, same block
    clocks, same running pool, so the next ``step_frame`` continues the
    preempted session as if it was never evicted."""
    idx = jnp.asarray(idx, jnp.int32)

    def s(leaf, sv):
        return leaf.at[idx].set(jnp.asarray(sv, leaf.dtype))

    blocks = [{k: s(v, sb[k]) for k, v in b.items()}
              for b, sb in zip(state.blocks, snap["blocks"])]
    rfc = None
    if state.rfc is not None:
        rfc = [{k: s(v, sr[k]) for k, v in r.items()}
               for r, sr in zip(state.rfc, snap["rfc"])]
    return StreamState(
        t_raw=s(state.t_raw, snap["t_raw"]), blocks=blocks,
        pool_ring=(s(state.pool_ring, snap["pool_ring"])
                   if state.pool_ring is not None else None),
        pool_sum=s(state.pool_sum, snap["pool_sum"]),
        pool_t=s(state.pool_t, snap["pool_t"]),
        bn_stats=state.bn_stats, rfc=rfc)


# sentinel slot/ring index marking a padded no-op event in the fixed-shape
# snapshot/restore order buffers consumed by fused_tick: far out of bounds
# for any slab or ring axis, so the gather clamps it (value discarded) and
# the scatter drops it — a padded event touches nothing
SNAP_SENTINEL = np.int32(2 ** 30)


def init_snapshot_ring(slab: StreamState, capacity: int) -> Dict[str, Any]:
    """Preallocated on-device snapshot ring: ``capacity`` rows, each shaped
    like one slot's :func:`snapshot_slots` capture.

    The ring replaces host-side per-event snapshot tuples in the fused
    serving tick (:func:`fused_tick`): preemption captures are scattered
    into ring rows and restores gather them back out, all inside one
    dispatch, with the host only tracking which row holds which session.
    Row shapes are per-slot (independent of the slab's capacity S), so one
    ring serves every capacity tier and survives elastic migrations."""
    idx = jnp.zeros((int(capacity),), jnp.int32)
    return jax.tree_util.tree_map(jnp.zeros_like, snapshot_slots(slab, idx))


def snapshot_to_ring(slab: StreamState, ring: Dict[str, Any],
                     order) -> Dict[str, Any]:
    """Apply a fixed-shape batch of snapshot events: for each (slot, row)
    pair in ``order``, gather slot ``slot``'s per-slot state out of the
    slab and write it into ring row ``row``.

    ``order`` is an (E, 2) int32 array padded with :data:`SNAP_SENTINEL`
    no-op rows, so any event count from 0 to E reuses one compilation —
    sentinel gathers clamp (their value is discarded) and sentinel
    scatters drop.  Returns the updated ring; the slab is read-only."""
    order = jnp.asarray(order, jnp.int32)
    S = slab.t_raw.shape[0]
    rows = snapshot_slots(slab, jnp.minimum(order[:, 0], S - 1))
    dst = order[:, 1]

    def put(r, x):
        return r.at[dst].set(jnp.asarray(x, r.dtype), mode="drop")

    return jax.tree_util.tree_map(put, ring, rows)


def restore_from_ring(slab: StreamState, ring: Dict[str, Any],
                      order) -> StreamState:
    """Apply a fixed-shape batch of restore events: for each (slot, row)
    pair in ``order``, gather ring row ``row`` and scatter it into slab
    slot ``slot`` — the inverse of :func:`snapshot_to_ring`, with the same
    :data:`SNAP_SENTINEL` padding semantics (sentinel events touch no
    slot).  Returns the updated slab; ring rows are read-only (a restored
    row's stale copy stays in the ring until the host reuses it)."""
    order = jnp.asarray(order, jnp.int32)
    slot = order[:, 0]
    R = ring["t_raw"].shape[0]
    src = jnp.minimum(order[:, 1], R - 1)

    def g(leaf):
        return jnp.take(leaf, src, axis=0, mode="clip")

    def s(leaf, sv):
        return leaf.at[slot].set(jnp.asarray(sv, leaf.dtype), mode="drop")

    blocks = [{k: s(v, g(rb[k])) for k, v in b.items()}
              for b, rb in zip(slab.blocks, ring["blocks"])]
    rfc = None
    if slab.rfc is not None:
        rfc = [{k: s(v, g(rr[k])) for k, v in r.items()}
               for r, rr in zip(slab.rfc, ring["rfc"])]
    return StreamState(
        t_raw=s(slab.t_raw, g(ring["t_raw"])), blocks=blocks,
        pool_ring=(s(slab.pool_ring, g(ring["pool_ring"]))
                   if slab.pool_ring is not None else None),
        pool_sum=s(slab.pool_sum, g(ring["pool_sum"])),
        pool_t=s(slab.pool_t, g(ring["pool_t"])),
        bn_stats=slab.bn_stats, rfc=rfc)


def fused_tick(
    plan: ExecutionPlan,
    slab: StreamState,
    frames: jnp.ndarray,             # (S, V, C) one raw frame per slot
    valid,                           # (S,) bool — per-slot clip/flush phase
    reset,                           # (S,) bool — admission reset
    hold,                            # (S,) bool — freeze starved open slots
    snap_order,                      # (E, 2) int32 (slot, ring row) padded
    rest_order,                      # (E, 2) int32 (slot, ring row) padded
    snap_ring: Dict[str, Any],       # init_snapshot_ring state
    bn_stats: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Tuple[StreamState, jnp.ndarray, Dict[str, Any]]:
    """One serving tick as a single device dispatch: snapshot gathers,
    restore scatters, admission resets, hold masking and the slab step,
    fused — returns ``(slab, logits, snap_ring)``.

    The multi-dispatch tick (one jitted call per snapshot event, one per
    restore event, then :func:`step_frames`) becomes one jitted function:
    ``snap_order``/``rest_order`` are fixed-shape (E, 2) traced index
    arrays padded with :data:`SNAP_SENTINEL` no-ops, so *any* per-tick
    event count reuses one compilation per slab capacity, and the captures
    live in the preallocated on-device ``snap_ring`` instead of host-side
    Python tuples.  Event semantics match the multi-dispatch sequence:
    snapshots gather from the **pre-tick** slab (capture before restore),
    restores scatter ring rows written this tick or earlier (a same-tick
    snapshot→restore resumes correctly), then ``reset`` zeroes fresh
    admissions before their first frame lands.

    Built for donation: jit it with the slab and ring donated
    (``donate_argnums``) so XLA updates the rings in place — after the
    call the *input* slab/ring buffers are dead and the caller must only
    ever touch the returned ones."""
    new_ring = snapshot_to_ring(slab, snap_ring, snap_order)
    slab = restore_from_ring(slab, new_ring, rest_order)
    new_slab, logits = step_frames(plan, slab, frames, valid, reset, hold,
                                   bn_stats=bn_stats)
    return new_slab, logits, new_ring


def stream_flush_frames(plan: ExecutionPlan, frames: int) -> int:
    """Raw flush steps (zero frames, valid=False) needed after a ``frames``-
    long clip so the final valid output drains through every block's
    ``pad``-frame latency — after which streaming logits equal clip logits."""
    ps = plan.static
    pad = ps.tkernel // 2
    t = -(-frames // ps.input_skip)            # frames surviving input skip
    for bs in ps.blocks:
        t = (t - 1) // bs.stride + 1           # clip-mode output length
    o = t - 1                                  # last valid final-block output
    for bs in reversed(ps.blocks):
        o = o * bs.stride + pad                # input index that triggers it
    total = o * ps.input_skip + 1
    return max(0, total - frames)


def stream_first_logit_delay(plan: ExecutionPlan) -> int:
    """Raw frames from slot admission until the first *valid* logit
    contribution lands in the pool — the admission-to-first-logit latency
    in frame ticks (the wall-clock version is measured by the session
    scheduler).  Same backward recurrence as :func:`stream_flush_frames`
    with final output index o = 0."""
    ps = plan.static
    pad = ps.tkernel // 2
    o = 0
    for bs in reversed(ps.blocks):
        o = o * bs.stride + pad
    return o * ps.input_skip + 1


def _pooled_logits(arrays, ps: PlanStatic, pool_sum, pool_t) -> jnp.ndarray:
    """Running prediction from the temporal logit pool: mean over the
    effective pooled-frame count (clamped to the sliding window when
    ``stream_pool`` > 0, and to 1 before the first contribution), through
    the fc head.  Shared by the streaming step and the hold path so the
    two can never desynchronize."""
    n_eff = (jnp.minimum(pool_t, ps.stream_pool) if ps.stream_pool > 0
             else pool_t)
    pooled = pool_sum / jnp.maximum(n_eff, 1)[:, None].astype(pool_sum.dtype)
    return pooled @ arrays["fc_w"] + arrays["fc_b"]


def _stem_frame(arrays, frame: jnp.ndarray, bn) -> jnp.ndarray:
    """Per-frame stem: data_bn on one (N, V, C) frame with frozen stats."""
    x = frame.astype(arrays["data_bn"]["scale"].dtype)
    N, V, C = x.shape
    h = x.reshape(N, V * C)
    return bn("data_bn", h, arrays["data_bn"]).reshape(N, V, C)


def step_frame(
    plan: ExecutionPlan,
    state: StreamState,
    frame: jnp.ndarray,              # (S, V, C) one raw frame per slot
    valid=True,                      # False -> flush step (post-clip drain)
    bn_stats: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Tuple[StreamState, jnp.ndarray]:
    """Advance every stream slot by one raw frame; returns (state, logits).

    ``valid`` is a scalar (lockstep batch — every slot streams the same
    clip timeline) or a (S,) bool vector (session slab — each slot has its
    own clip/flush phase; False slots take the zero-padding drain path).
    Because every clock in the state is per-slot, slots admitted at
    different times decimate, emit and pool independently.

    ``bn_stats`` overrides the slab's frozen calibration for this step —
    the multi-topology service runs one dispatch per skeleton group over
    the same slab, each with its own topology's statistics (padded to the
    slab width here).  ``None`` keeps the state's own stats (the single-
    topology path, unchanged).

    A plan whose topology is narrower than the slab (``valid_joints`` <
    ``joints``) masks the padded joint rows after the stem and after each
    block's ReLUs — BN bias would otherwise leak nonzero values into them
    — and pools logits over the valid joints only, so a session's logits
    equal its dedicated narrow-slab run.

    Pure and jit-stable: the plan and state ride as pytree arguments, all
    data-dependent control (input-skip gaps, stride-decimated emission,
    clip-validity of flushed windows, per-slot ring phases) is traced
    masking — one compilation per ExecutionPlan serves the whole stream at
    any slot occupancy.  The slot axis is constrained to the logical
    "batch" sharding axis, so a slab shards across devices under
    ``distributed.sharding.axis_rules``."""
    from repro.distributed.sharding import constrain

    ps = plan.static
    backend = get_backend(ps.backend, ps.interpret)
    stats = (state.bn_stats if bn_stats is None
             else _pad_data_bn_stats(bn_stats, ps))
    bn = _BNFrozen(stats)
    K = ps.tkernel
    pad = K // 2
    nblocks = len(ps.blocks)
    S = frame.shape[0]
    rows = jnp.arange(S)
    vj = ps.valid_joints or ps.joints
    vmask = vj < ps.joints               # mask padded joints (static)

    valid = jnp.broadcast_to(jnp.asarray(valid, bool), (S,))
    process = (state.t_raw % ps.input_skip) == 0      # C5 input skipping (S,)
    has_input = process
    in_valid = jnp.logical_and(valid, process)
    frame = constrain(frame, "batch", None, None)
    h_in = _stem_frame(plan.arrays, frame, bn)
    if vmask:
        h_in = h_in.at[:, vj:, :].set(0.0)

    new_blocks: List[Dict[str, Any]] = []
    new_rfc: List[Dict[str, Any]] = []
    emit = has_input
    out = h_in
    out_valid = in_valid
    for b, (ba, bs) in enumerate(zip(plan.arrays["blocks"], ps.blocks)):
        sb = state.blocks[b]
        tag = f"b{b}/"
        t = sb["t"]                                    # (S,) block clock
        slot = t % K                                   # (S,) ring phase

        # --- windowed C_k: embedding-ring update + graph (adaptive.py) ----
        ck = None
        ck_th = ck_ph = None
        if bs.use_ck:
            xg = _gather_in(h_in, ba)
            e_th = jnp.einsum("nvc,ce->nve", xg,
                              ba["theta"].astype(h_in.dtype))
            e_ph = jnp.einsum("nvc,ce->nve", xg,
                              ba["phi"].astype(h_in.dtype))
            # invalid (flush) frames write zero embeddings — they trail
            # every valid frame, so valid windows match clip mode exactly
            e_th = jnp.where(in_valid[:, None, None], e_th, 0.0)
            e_ph = jnp.where(in_valid[:, None, None], e_ph, 0.0)
            ck_th = jnp.where(has_input[:, None, None, None],
                              sb["ck_th"].at[rows, slot].set(e_th),
                              sb["ck_th"])
            ck_ph = jnp.where(has_input[:, None, None, None],
                              sb["ck_ph"].at[rows, slot].set(e_ph),
                              sb["ck_ph"])
            vjs = vj if vmask else 0
            if ps.backend == "pallas":
                ck = shard_batch(
                    lambda r: ops.windowed_similarity(
                        *r, valid_joints=vjs, interpret=ps.interpret),
                    (ck_th, ck_ph))
            else:
                ck = adaptive.windowed_ck(ck_th.sum(axis=1),
                                          ck_ph.sum(axis=1),
                                          valid_joints=vjs)

        # --- frame-local gcn unit (spatial graph conv + down residual) ----
        s = backend.spatial(h_in[:, None], ba, bs,
                            ck=None if ck is None else ck[:, None])[:, 0]
        s = bn(tag + "bn_s", s, ba["bn_s"])
        down = (bn(tag + "bn_down",
                   jnp.einsum("nvc,co->nvo", h_in, ba["down_w"]),
                   ba["bn_down"])
                if ba["down_w"] is not None else h_in)
        s = jax.nn.relu(s + down)
        if vmask:          # BN bias injects nonzero values at padded joints
            s = s.at[:, vj:, :].set(0.0)
        # invalid inputs become the clip conv's zero padding at this level
        s = jnp.where(in_valid[:, None, None], s, 0.0)

        # --- masked per-slot ring write ----------------------------------
        ring_s = jnp.where(has_input[:, None, None, None],
                           sb["ring_s"].at[rows, slot].set(s), sb["ring_s"])
        ring_h = jnp.where(has_input[:, None, None, None],
                           sb["ring_h"].at[rows, slot].set(h_in),
                           sb["ring_h"])
        vring = jnp.where(has_input[:, None],
                          sb["valid"].at[rows, slot].set(in_valid),
                          sb["valid"])
        t_new = t + has_input.astype(jnp.int32)
        nb = {"ring_s": ring_s, "ring_h": ring_h,
              "valid": vring, "t": t_new}
        if bs.use_ck:
            nb["ck_th"] = ck_th
            nb["ck_ph"] = ck_ph
        new_blocks.append(nb)

        # --- stride-decimated emission (per slot) ------------------------
        # output o of the clip conv completes when input t = o*stride + pad
        # arrives; its center tap (and residual source) is input t - pad
        emit = jnp.logical_and(
            has_input,
            jnp.logical_and(t >= pad, (t - pad) % bs.stride == 0))
        idx = (t[:, None] + 1 + jnp.arange(K)[None, :]) % K   # (S, K) chrono
        win = jnp.take_along_axis(ring_s, idx[:, :, None, None], axis=1)
        out = backend.temporal_step(win, ba, bs)
        out = bn(tag + "bn_t", out, ba["bn_t"])
        center = (t - pad) % K                         # (S,)
        h_c = jnp.take_along_axis(
            ring_h, center[:, None, None, None], axis=1)[:, 0]
        if ba["short_w"] is not None:
            res = bn(tag + "bn_short",
                     jnp.einsum("nvc,co->nvo", h_c, ba["short_w"]),
                     ba["bn_short"])
        else:
            res = h_c
        out = jax.nn.relu(out + res)
        if vmask:
            out = out.at[:, vj:, :].set(0.0)
        out_valid = jnp.take_along_axis(vring, center[:, None], axis=1)[:, 0]

        # --- inter-block transfer: the RFC format, frame-wise -------------
        if b < nblocks - 1:
            if ps.use_rfc:
                vals, hot = _rfc_encode(out, ps)
                old = state.rfc[b]
                keep = emit[:, None, None]
                new_rfc.append(
                    {"vals": jnp.where(keep, vals, old["vals"]),
                     "hot": jnp.where(keep, hot, old["hot"])})
                out = _rfc_decode((vals, hot), ps)
            h_in = out
        has_input = emit
        in_valid = out_valid

    # --- running temporal logit pool (per slot) ---------------------------
    take = jnp.logical_and(emit, out_valid)            # (S,)
    contrib = out[:, :vj].mean(axis=1)                 # (S, C_last): valid
                                                       # joints pooled
    if ps.stream_pool > 0:
        W = ps.stream_pool
        pslot = state.pool_t % W                       # (S,)
        pool_ring = jnp.where(
            take[:, None, None],
            state.pool_ring.at[rows, pslot].set(contrib), state.pool_ring)
        # recompute from the ring (W is small): a running add/subtract
        # would accumulate rounding drift over an unbounded live stream
        pool_sum = pool_ring.sum(axis=1)
        pool_t = state.pool_t + take.astype(jnp.int32)
    else:
        pool_ring = None
        pool_sum = state.pool_sum + jnp.where(take[:, None], contrib, 0.0)
        pool_t = state.pool_t + take.astype(jnp.int32)
    logits = _pooled_logits(plan.arrays, ps, pool_sum, pool_t)
    logits = constrain(logits, "batch", None)

    new_state = StreamState(
        t_raw=state.t_raw + 1, blocks=new_blocks, pool_ring=pool_ring,
        pool_sum=pool_sum, pool_t=pool_t, bn_stats=state.bn_stats,
        rfc=new_rfc if ps.use_rfc else None)
    return new_state, logits


def step_frames(
    plan: ExecutionPlan,
    slab: StreamState,
    frames: jnp.ndarray,             # (S, V, C) one raw frame per slot
    valid,                           # (S,) bool — per-slot clip/flush phase
    reset=None,                      # optional (S,) bool — admission reset
    hold=None,                       # optional (S,) bool — freeze the slot
    bn_stats: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Tuple[StreamState, jnp.ndarray]:
    """One scheduler tick of the session slab; returns (slab, logits[S]).

    The multi-session serving step: ``reset`` zeroes the marked slots
    *before* the frame is consumed (so an admission's first frame lands in
    a clean ring), then every slot advances one raw frame with its own
    ``valid`` bit — active sessions feed real frames (True), draining
    sessions feed the zero-padding flush (False), and free slots are dead
    weight masked by the same validity machinery.  ``hold`` freezes the
    marked slots entirely: their per-slot state is untouched (no clock
    advance, no ring write — *not* the flush path, which would inject
    zero padding mid-stream) and their logits row is the previous running
    prediction.  This is how an open-ended session (``GcnService.submit``)
    starves gracefully when its frame buffer is empty but the stream has
    not been closed.  Everything is traced masking over the compiled
    :func:`step_frame`, so the jitted tick is compiled once per
    ExecutionPlan regardless of admissions, evictions, holds or occupancy.
    Logits row s is slot s's running prediction; the host-side scheduler
    (``repro.serving``) reads it at eviction time."""
    if reset is not None:
        slab = reset_slots(slab, reset)
    new, logits = step_frame(plan, slab, frames, valid, bn_stats=bn_stats)
    if hold is not None:
        from repro.distributed.sharding import constrain

        new = _select_slots(hold, slab, new)
        # recompute the logits from the selected pool: held slots report
        # their previous running prediction, all others are unchanged
        # (re-constrained to the slot axis like the hold=None path)
        logits = _pooled_logits(plan.arrays, plan.static, new.pool_sum,
                                new.pool_t)
        logits = constrain(logits, "batch", None)
    return new, logits
