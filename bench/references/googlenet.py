"""The plain reference of ``googlenet.json``: GoogLeNet's nine Inception
modules (Szegedy et al., "Going deeper with convolutions",
arXiv:1409.4842, Figure 2(b) and Table 1) in float32.

It reads the modules from the configuration file alone, six layers a
module in the order 1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj,
and imports nothing of the program.  Per module, on its input ``x``:

* a 3x3/2 max-pool first where the module's feature map is smaller than
  ``x`` (before 4a and 5a), in ceil mode as the published network's
  pools (28 -> 14 -> 7);
* four branches on ``x``: the 1x1; the 3x3 reduce then the 3x3; the 5x5
  reduce then the 5x5; a 3x3/1 max-pool (one pixel of -inf padding)
  then the pool proj;
* every convolution "same"-padded with zeros, grouped as the mapping
  groups it (``feature_group_count = groups``, kernels ``(k, k,
  ic / groups, oc)`` with the pruned per-group input channels zero),
  at ``Precision.HIGHEST``, then a ReLU;
* the four outputs concatenated on channels in that order.

Departures from the paper: the convolutions are grouped and pruned as
the TetrisG-SDK mapping makes them (the weights come from the seed, not
from training); there are no biases; the stem (7x7/2 conv, 3x3 convs,
LRN, pools), the average pool, dropout, the classifier and both
auxiliary classifiers are left out, as the configuration's
``leaves_out`` says.  The weights, the images and the useful FLOPs are
``bench/reference.py``'s.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import make_data, quantize, useful_flops_per_image  # noqa: F401

#: The six convolutions of a module: (name suffix, kernel size).
MODULE = (("1x1", 1), ("3x3r", 1), ("3x3", 3), ("5x5r", 1), ("5x5", 5),
          ("pool", 1))


def modules(config: dict):
    """The configuration's layers six at a time, each module checked
    against the published wiring; ``(feature size, the six layers)``."""
    layers = config["layers"]
    if len(layers) % len(MODULE):
        raise ValueError(f"{len(layers)} layers are not whole modules")
    out = []
    for m in range(0, len(layers), len(MODULE)):
        mod = layers[m:m + len(MODULE)]
        size = mod[0]["size"]
        for lay, (suffix, k) in zip(mod, MODULE):
            if (not lay["name"].endswith(suffix) or lay["kernel"] != k
                    or lay["stride"] != 1 or lay["size"] != size + k - 1):
                raise ValueError(f"{lay['name']}: not the {suffix} conv of "
                                 f"an Inception module at {size}x{size}")
        c1, r3, c3, r5, c5, pp = mod
        if (r3["oc"] != c3["ic"] or r5["oc"] != c5["ic"]
                or not c1["ic"] == r3["ic"] == r5["ic"] == pp["ic"]):
            raise ValueError(f"{c1['name']}: module channels do not wire")
        out.append((size, mod))
    return out


def conv_relu(x, lay: dict, k, operands: Optional[str]):
    """``lay``'s convolution on ``x``, zero-padded to keep its size, then
    a ReLU."""
    p = lay["kernel"] // 2
    y = lax.conv_general_dilated(
        quantize(x, operands), quantize(k, operands), window_strides=(1, 1),
        padding=((p, p), (p, p)),
        dimension_numbers=("NCHW", "HWIO", "NCHW"),
        feature_group_count=lay["groups"], precision=lax.Precision.HIGHEST)
    return jnp.maximum(y, 0.0)


def max_pool(x, stride: int):
    """3x3 max-pool: stride 1 with one -inf pixel each side, or stride 2
    in ceil mode (the last window may hang past the far edge)."""
    if stride == 1:
        pads = ((1, 1), (1, 1))
    else:
        pads = tuple((0, 2 * (-(-(n - 3) // 2)) + 3 - n)
                     for n in x.shape[2:])
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, stride, stride), ((0, 0), (0, 0)) + pads)


def forward(config: dict, kernels: Sequence, x, *,
            operands: Optional[str] = None):
    """The nine modules on a batch ``x (B, 192, 28, 28)``; with
    ``operands`` each convolution's input and kernel are first rounded
    to that type (the control)."""
    ks = iter(kernels)
    for size, mod in modules(config):
        while x.shape[2] > size:
            x = max_pool(x, 2)
        if x.shape[2:] != (size, size):
            raise ValueError(f"{mod[0]['name']}: input {x.shape[2:]} for a "
                             f"module at {size}x{size}")
        c1, r3, c3, r5, c5, pp = mod
        b1 = conv_relu(x, c1, next(ks), operands)
        b3 = conv_relu(conv_relu(x, r3, next(ks), operands), c3, next(ks),
                       operands)
        b5 = conv_relu(conv_relu(x, r5, next(ks), operands), c5, next(ks),
                       operands)
        bp = conv_relu(max_pool(x, 1), pp, next(ks), operands)
        x = jnp.concatenate([b1, b3, b5, bp], axis=1)
    return x


_forward_jit = jax.jit(forward, static_argnums=(0,),
                       static_argnames=("operands",))


class _Frozen(dict):
    """A configuration as a hashable static jit argument."""

    def __hash__(self):
        return hash(repr(self["layers"]))


def forward_blocks(config: dict, kernels, rows: np.ndarray, *,
                   block: int, operands: Optional[str] = None) -> np.ndarray:
    """:func:`forward` over host ``rows`` in blocks of ``block`` images
    (the last one zero-padded); the outputs on the host."""
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
