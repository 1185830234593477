"""The plain reference of ``residual_toy.json``: its layers in float32,
joined by the glue each layer carries.

Per layer, in the program's order: the spatial adapter
(``reference.fit_spatial``); ``save`` keeps that adapted input; the
convolution; then ``kind`` forms the next input — ``chain`` and
``last`` pass the output on, ``residual`` adds the last saved input to
it, ``concat`` puts the center-cropped input before it.  The weights,
the images and the useful FLOPs are ``bench/reference.py``'s.  It
imports nothing of the program.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import (fit_spatial, make_data,  # noqa: F401
                       quantize, useful_flops_per_image)

#: The glue this reference computes; any other field is refused.
GLUE_FIELDS = ("kind", "save")


def forward(config: dict, kernels: Sequence, x, *,
            operands: Optional[str] = None):
    """The layer graph on a batch ``x (B, C, H, W)``, every convolution
    at ``Precision.HIGHEST``, its operands first rounded to
    ``operands`` where given (the control)."""
    saved = []
    for lay, k in zip(config["layers"], kernels):
        glue = lay.get("glue")
        if glue is None or set(glue) - set(GLUE_FIELDS):
            raise ValueError(f"{lay['name']}: glue {glue!r}; this reference "
                             f"takes only {GLUE_FIELDS}")
        xp = fit_spatial(x, lay["size"])
        if glue.get("save", False):
            saved.append(xp)
        y = lax.conv_general_dilated(
            quantize(xp, operands), quantize(k, operands),
            window_strides=(lay["stride"], lay["stride"]), padding="VALID",
            dimension_numbers=("NCHW", "HWIO", "NCHW"),
            feature_group_count=lay["groups"],
            precision=lax.Precision.HIGHEST)
        kind = glue.get("kind", "chain")
        if kind == "residual":
            x = saved.pop() + y
        elif kind == "concat":
            h0 = (xp.shape[2] - y.shape[2]) // 2
            w0 = (xp.shape[3] - y.shape[3]) // 2
            x = jnp.concatenate([xp[:, :, h0:h0 + y.shape[2],
                                    w0:w0 + y.shape[3]], y], axis=1)
        else:
            x = y
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
