"""jit'd public wrappers for the Pallas kernels.

Interpret mode (the Pallas interpreter runs the kernel bodies in Python)
is decided per call from the platform the call runs on: compiled Mosaic
kernels on a TPU, the interpreter anywhere else.  Nothing is decided at
import, so importing this module never starts a JAX backend.  Callers
can still force either mode per call.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax

from .flash_attention import flash_attention
from .grouped_matmul import grouped_matmul
from .tetris_matmul import tetris_matmul


def _interpret(x, interpret: Optional[bool]) -> bool:
    """``interpret``, or when None: whether the platform operand ``x``
    lives on (a concrete array's device, else the default backend) is
    not a TPU."""
    if interpret is not None:
        return interpret
    try:
        platform = next(iter(x.devices())).platform
    except (AttributeError, TypeError, StopIteration):
        platform = jax.default_backend()
    return platform != "tpu"


@partial(jax.jit, static_argnames=("block", "interpret"))
def _matmul(x, w, block, interpret):
    return tetris_matmul(x, w, block=block, interpret=interpret)


def matmul(x, w, block: Optional[Tuple[int, int, int]] = None,
           interpret: Optional[bool] = None):
    return _matmul(x, w, block=block, interpret=_interpret(x, interpret))


@partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def _gmm(x, w, bm, bf, interpret):
    return grouped_matmul(x, w, bm=bm, bf=bf, interpret=interpret)


def gmm(x, w, bm: Optional[int] = None, bf: Optional[int] = None,
        interpret: Optional[bool] = None):
    return _gmm(x, w, bm=bm, bf=bf, interpret=_interpret(x, interpret))


@partial(jax.jit, static_argnames=("causal", "q_offset", "interpret"))
def _attention(q, k, v, causal, q_offset, interpret):
    return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                           interpret=interpret)


def attention(q, k, v, causal: bool = True, q_offset: int = 0,
              interpret: Optional[bool] = None):
    return _attention(q, k, v, causal=causal, q_offset=q_offset,
                      interpret=_interpret(q, interpret))
