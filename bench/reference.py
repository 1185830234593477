"""The plain reference: a configuration's served chain in float32.

It reads the chain from the configuration file alone (``layers``: the
padded input size, kernel, channels, stride, the grouping factor the
mapping chose and the per-group input channels it prunes) and imports
nothing of the program.  What it mirrors of the program's chain:

* grouped convolution, ``feature_group_count = groups``, kernels in the
  grouped layout ``(k, k, ic / groups, oc)`` with the pruned per-group
  input channels zero;
* the spatial adapter between layers: 2x2 max-pool while the carry is at
  least twice the next layer's padded input, then center pad or crop;
* the channel glue: the next layer takes this layer's output when its
  ``ic`` equals this ``oc`` (a chain), or the center-cropped input of
  this layer concatenated with its output when its ``ic`` is their sum
  (DenseNet's dense block);
* no activation, norm or bias: the served chain has none.

The weights and the image pool come from the seed here, on the device,
and the benchmark hands the same arrays to the program.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Precisions a control may compute its operands in.
CONTROL_DTYPES = ("bfloat16", "int8")


def jax_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``, any integer (64 bits kept)."""
    s = seed % 2**64
    key = jax.random.fold_in(jax.random.PRNGKey(s % 2**32), s >> 32)
    return jax.random.fold_in(key, stream)


def kernel_shape(layer: dict):
    k, g = layer["kernel"], layer["groups"]
    if layer["ic"] % g or layer["oc"] % g:
        raise ValueError(f"{layer['name']}: groups {g} must divide "
                         f"ic={layer['ic']} and oc={layer['oc']}")
    return (k, k, layer["ic"] // g, layer["oc"])


def kept_mask(layer: dict) -> np.ndarray:
    """1 for each per-group input channel the chain keeps, 0 if pruned."""
    mask = np.ones(kernel_shape(layer)[2], np.float32)
    for start, count in layer["pruned"]:
        mask[start:start + count] = 0.0
    return mask


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _make(shapes, masks, pool_shape, key):
    keys = jax.random.split(key, len(shapes) + 1)
    ks = []
    for sh, mask, k in zip(shapes, masks, keys[:-1]):
        fan_in = sh[0] * sh[1] * sum(mask)
        w = jax.random.normal(k, sh, jnp.float32) * (1.0 / np.sqrt(fan_in))
        ks.append(w * jnp.asarray(mask, jnp.float32)[None, None, :, None])
    pool = jax.random.normal(keys[-1], pool_shape, jnp.float32)
    return tuple(ks), pool


def make_data(config: dict, pool_images: int, seed: int):
    """``(kernels, pool)`` on the device in one program from ``seed``:
    each layer's grouped kernel, N(0, 1/fan_in) with its pruned channels
    zero, and ``pool_images`` input images N(0, 1)."""
    layers = config["layers"]
    shapes = tuple(kernel_shape(l) for l in layers)
    masks = tuple(tuple(float(v) for v in kept_mask(l)) for l in layers)
    return _make(shapes, masks, (pool_images, *config["input"]),
                 jax_key(seed, 0))


def fit_spatial(x, size: int):
    while x.shape[-2] >= 2 * size and x.shape[-1] >= 2 * size:
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                              (1, 1, 2, 2), "VALID")
    for ax in (2, 3):
        d = size - x.shape[ax]
        if d > 0:
            pad = [(0, 0)] * 4
            pad[ax] = (d // 2, d - d // 2)
            x = jnp.pad(x, pad)
        elif d < 0:
            x = lax.slice_in_dim(x, (-d) // 2, (-d) // 2 + size, axis=ax)
    return x


def _center_crop(x, h: int, w: int):
    y0 = (x.shape[2] - h) // 2
    x0 = (x.shape[3] - w) // 2
    return x[:, :, y0:y0 + h, x0:x0 + w]


def quantize(x, dtype: Optional[str]):
    """``x`` rounded to ``dtype`` and back to float32: bfloat16 as is;
    int8 with one symmetric scale per tensor that maps its largest
    magnitude to 127, in float32 arithmetic (the same on every
    platform)."""
    if dtype is None:
        return x
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if dtype != "int8":
        raise ValueError(f"no control operands {dtype!r}: "
                         f"one of {CONTROL_DTYPES}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


def forward(config: dict, kernels: Sequence, x, *,
            operands: Optional[str] = None):
    """The chain on a batch ``x (B, C, H, W)``: float32 with every
    convolution at ``Precision.HIGHEST``; with ``operands`` (a
    :data:`CONTROL_DTYPES` name) each convolution's input and kernel are
    first rounded to that type — the control."""
    layers = config["layers"]
    for i, (lay, k) in enumerate(zip(layers, kernels)):
        xp = fit_spatial(x, lay["size"])
        y = lax.conv_general_dilated(
            quantize(xp, operands), quantize(k, operands),
            window_strides=(lay["stride"], lay["stride"]), padding="VALID",
            dimension_numbers=("NCHW", "HWIO", "NCHW"),
            feature_group_count=lay["groups"],
            precision=lax.Precision.HIGHEST)
        if i + 1 == len(layers):
            return y
        nxt = layers[i + 1]["ic"]
        if nxt == lay["oc"]:
            x = y
        elif nxt == xp.shape[1] + lay["oc"]:
            x = jnp.concatenate(
                [_center_crop(xp, y.shape[2], y.shape[3]), y], axis=1)
        else:
            raise ValueError(f"cannot chain {lay['name']} (oc={lay['oc']},"
                             f" carry={xp.shape[1]}) into ic={nxt}")
    raise ValueError("empty chain")


_forward_jit = jax.jit(forward, static_argnums=(0,),
                       static_argnames=("operands",))


class _Frozen(dict):
    """A configuration as a hashable static jit argument."""

    def __hash__(self):
        return hash(repr(self["layers"]))


def forward_blocks(config: dict, kernels, rows: np.ndarray, *,
                   block: int, operands: Optional[str] = None) -> np.ndarray:
    """:func:`forward` over host ``rows`` in blocks of ``block`` images
    (the last one zero-padded), so one compiled program covers any
    count; the outputs on the host."""
    cfg = _Frozen(config)
    out: List[np.ndarray] = []
    for s in range(0, len(rows), block):
        part = rows[s:s + block]
        n = len(part)
        if n < block:
            part = np.concatenate(
                [part, np.zeros((block - n,) + part.shape[1:], part.dtype)])
        y = _forward_jit(cfg, tuple(kernels), jnp.asarray(part),
                         operands=operands)
        out.append(np.asarray(y)[:n])
    return np.concatenate(out)


def output_size(layer: dict) -> int:
    return (layer["size"] - layer["kernel"]) // layer["stride"] + 1


def useful_flops_per_image(config: dict) -> float:
    """Multiply-adds (x2) of the grouped, pruned convolutions the mapping
    defines: each output takes ``k*k`` taps over the kept channels of
    its group."""
    total = 0
    for lay in config["layers"]:
        o = output_size(lay)
        kept = int(kept_mask(lay).sum())
        total += 2 * o * o * lay["oc"] * lay["kernel"] ** 2 * kept
    return float(total)


def rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    """``max|y - ref| / max|ref|`` in float64; inf where ``y`` is not
    finite or has another shape."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(y - ref)))
    return err / scale if scale else err
