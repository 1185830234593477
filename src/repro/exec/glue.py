"""Inter-layer glue: the deterministic adapters between mapped layers.

A `NetworkMapping` chains layers whose padded specs rarely line up
exactly; the glue closes the gap in two orthogonal directions:

* **spatial** — :func:`fit_spatial` 2x2-max-pools while the carry is
  >= 2x the next layer's (padded) input, then center-pads / center-crops
  to the exact size.  Deterministic in the *shapes* only, so it is
  resolvable at plan-compile time and traces to a static op chain.
* **channel** — :func:`resolve_chain` classifies how layer i feeds
  layer i+1 from pure channel arithmetic: ``"chain"`` when the next
  layer's ic equals this layer's oc, ``"concat"`` (DenseNet-style: the
  layer's unpadded input is concatenated with its output) when it
  equals their sum, and a clear error otherwise.

Both are mirrored by the reference composition (`reference_net_apply`)
so equivalence tests compare executors, not plumbing.

Since the operator-generic refactor (ISSUE 8, DESIGN.md §11) glue is a
structured `repro.core.GlueSpec` — ``kind`` is the carry rule below,
plus optional per-layer stages the CIM macros do not execute: ``pre``
layernorm passthrough (:func:`layernorm`), ``act`` activations
(:data:`ACTIVATIONS`), ``save``/``kind="residual"`` for transformer
residual adds, the ``post="attention"`` opaque stage
(:func:`attention_stage`) that turns a fused qkv projection's output
into attention context via `kernels.flash_attention` between two mapped
matmuls, and the multi-branch module glue of GoogLeNet's Inception
modules: ``pool`` runs a 3x3 max-pool before the spatial adapter
(:func:`stage_pool`), and ``kind="branch"``/``"join"`` collect the
branch outputs (each branch hands the next layer the module input
again) and concatenate them.  :func:`glue_floats` counts what the glue
writes, from the static shapes alone.  This module stays a leaf — jax +
kernels only — so every executor layer can import it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import GlueSpec  # noqa: F401  (re-export)

#: Post-layer carry updates a plan can prescribe (LayerPlan.glue.kind):
#: "chain" — carry becomes the layer's output; "concat" — carry becomes
#: concat(center-cropped layer input, output); "residual" — carry becomes
#: saved input + output (transformer skip); "branch" — the output joins
#: the module's open branches and the carry becomes the innermost saved
#: input (the module's input) again; "join" — the last branch: carry becomes
#: the branches concatenated in layer order, and the module's saved
#: input is popped; "last" — final layer, the output IS the result.
GLUE_KINDS = ("chain", "concat", "residual", "branch", "join", "last")

#: Per-layer glue activations (GlueSpec.act).  A layer whose glue names
#: one overrides any network-global ``activation`` for that layer.
ACTIVATIONS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
               "silu": jax.nn.silu}


def layernorm(x: jnp.ndarray, axis: int = 1,
              eps: float = 1e-5) -> jnp.ndarray:
    """Parameter-free layernorm over the channel axis (GlueSpec.pre).
    The mapped-serving lowering keeps norms outside the CIM macros as
    passthrough stages (geometry over weights — rmsnorm configs lower
    here too); learned scale/bias would fold into the next matmul's
    mapped weights, not into glue."""
    mu = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def attention_stage(y: jnp.ndarray, heads, causal: bool, *,
                    interpret: bool = False) -> jnp.ndarray:
    """The opaque attention stage (GlueSpec.post="attention"): consume a
    fused qkv projection's output ``y (B, (hq+2*hkv)*hd, M, 1)`` and
    return context ``(B, hq*hd, M, 1)`` for the mapped O projection.

    Runs `kernels.flash_attention.mha_flash` when M tiles by its block
    constraint (any M <= 128, or M % 128 == 0), else falls back to the
    plain-softmax oracle — the stage is glue, not a mapped layer, so
    cycle accounting is unaffected either way."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import ref
    hq, hkv, hd = heads
    b, c, m, w = y.shape
    if w != 1 or c != (hq + 2 * hkv) * hd:
        raise ValueError(f"attention_stage: qkv output {y.shape} != "
                         f"(B, {(hq + 2 * hkv) * hd}, M, 1) for "
                         f"heads={heads}")
    tok = y[..., 0].transpose(0, 2, 1)                   # (B, M, C)
    q = tok[..., :hq * hd].reshape(b, m, hq, hd)
    k = tok[..., hq * hd:(hq + hkv) * hd].reshape(b, m, hkv, hd)
    v = tok[..., (hq + hkv) * hd:].reshape(b, m, hkv, hd)
    if m <= 128 or m % 128 == 0:
        o = fa.mha_flash(q, k, v, causal=causal, interpret=interpret)
    else:                                   # ragged long seq: oracle path
        qf = q.transpose(0, 2, 1, 3).reshape(b * hq, m, hd)
        rep = hq // hkv
        kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
        vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
        o = ref.flash_attention_ref(
            qf, kf.reshape(b * hq, m, hd), vf.reshape(b * hq, m, hd),
            causal=causal).reshape(b, hq, m, hd).transpose(0, 2, 1, 3)
    return o.reshape(b, m, hq * hd).transpose(0, 2, 1)[..., None]


def fit_spatial(x: jnp.ndarray, i_h: int, i_w: int) -> jnp.ndarray:
    """Deterministic inter-layer adapter: 2x2 max-pool while the feature
    map is >= 2x the next layer's (padded) input, then center pad / crop
    to the exact size.  Mirrored by the reference composition so the
    cross-check compares executors, not plumbing."""
    while x.shape[-2] >= 2 * i_h and x.shape[-1] >= 2 * i_w:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    for ax, tgt in ((-2, i_h), (-1, i_w)):
        d = tgt - x.shape[ax]
        if d > 0:
            pad = [(0, 0)] * x.ndim
            pad[ax] = (d // 2, d - d // 2)
            x = jnp.pad(x, pad)
        elif d < 0:
            lo = (-d) // 2
            x = jax.lax.slice_in_dim(x, lo, lo + tgt, axis=x.ndim + ax)
    return x


def pool_size(n: int, pool: str) -> int:
    """Spatial size after a ``GlueSpec.pool`` stage: 3x3/1 with one
    pixel of -inf padding keeps it; 3x3/2 in ceil mode gives
    ``ceil((n - 3) / 2) + 1`` (28 -> 14 -> 7, GoogLeNet's stage pools)."""
    if pool == "none" or pool == "max3s1":
        return n
    return -(-(n - 3) // 2) + 1


def stage_pool(x: jnp.ndarray, pool: str) -> jnp.ndarray:
    """The ``GlueSpec.pool`` stage on ``x (B, C, H, W)``: a 3x3 max-pool,
    stride 1 with one -inf pixel each side (the Inception pool branch),
    or stride 2 in ceil mode, -inf padding only past the far edge."""
    if pool == "none":
        return x
    if pool == "max3s1":
        pads = ((1, 1), (1, 1))
        stride = 1
    else:
        pads = tuple((0, 2 * (pool_size(n, pool) - 1) + 3 - n)
                     for n in x.shape[-2:])
        stride = 2
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, stride, stride),
        ((0, 0), (0, 0)) + pads)


def glue_floats(layers, specs) -> int:
    """The floats per image that the glue between ``layers``
    (``ConvLayerSpec``, in plan order) writes, joined by ``specs`` (one
    resolved ``GlueSpec`` each), from the static shapes with the network
    input at the first layer's padded size: each pool (a ``pool`` stage
    and the adapter's 2x2 pools), each pad or crop of one axis, the
    DenseNet skip's crop and concat, and a join's concat.  Activations,
    norms, residual adds and the attention stage are not counted."""
    first = layers[0]
    c, h, w = first.ic, first.i_h, first.i_w
    saved, branches = [], []
    written = 0
    for lay, g in zip(layers, specs):
        if g.pool != "none":
            h, w = pool_size(h, g.pool), pool_size(w, g.pool)
            written += c * h * w
        while h >= 2 * lay.i_h and w >= 2 * lay.i_w:    # fit_spatial
            h, w = h // 2, w // 2
            written += c * h * w
        if h != lay.i_h:
            h = lay.i_h
            written += c * h * w
        if w != lay.i_w:
            w = lay.i_w
            written += c * h * w
        if g.save:
            saved.append((c, h, w))
        oh, ow = lay.o_h, lay.o_w
        oc = g.heads[0] * g.heads[2] if g.post == "attention" else lay.oc
        if g.kind == "concat":
            if (h, w) != (oh, ow):
                written += c * oh * ow
            written += (c + oc) * oh * ow
            c, h, w = c + oc, oh, ow
        elif g.kind == "join":
            c, h, w = sum(branches) + oc, oh, ow
            written += c * h * w
            saved.pop()
            branches = []
        elif g.kind == "branch":
            branches.append(oc)
            c, h, w = saved[-1]
        else:
            if g.kind == "residual":
                saved.pop()
            c, h, w = oc, oh, ow
    return written


def center_crop(x: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    """Center (h, w) spatial slice of x (..., H, W) with H >= h, W >= w."""
    y0 = (x.shape[-2] - h) // 2
    x0 = (x.shape[-1] - w) // 2
    return x[..., y0:y0 + h, x0:x0 + w]


def resolve_chain(name: str, oc: int, carry_c: int,
                  nxt_name: str, nxt_ic: int) -> str:
    """Classify how a layer with ``oc`` output channels (and ``carry_c``
    carried input channels) feeds the next layer: ``"chain"`` or
    ``"concat"``.  Raises the chaining error on any other arithmetic —
    at plan-compile time, not mid-forward."""
    if nxt_ic == oc:
        return "chain"
    if nxt_ic == carry_c + oc:
        return "concat"
    raise ValueError(
        f"cannot chain {name} (oc={oc}, carry={carry_c}) into "
        f"{nxt_name} (ic={nxt_ic})")
