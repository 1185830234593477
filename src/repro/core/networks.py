"""Benchmark network conv-layer specs (paper §IV-A, Table I).

IFM sizes are the *padded* sizes used by the paper's tables (CNN8 and
Inception rows reproduce Table I exactly: e.g. CNN8-2 is an 18x18 IFM
for a 16x16 feature map under 3x3/pad-1).  DenseNet40 / MobileNet follow
their standard literature configurations; where the paper under-specifies
(it reports only totals), the construction is documented inline.

The same specs feed every stage of the pipeline: the mapping searches
(core/mapper.py), the simulator (§IV-D), the trained CNNs
(cnn/models.py builds its stacks from ``ConvLayerSpec``) and the
mapped-network executor (cnn/mapped_net.py chains these stacks
layer-by-layer — plain for CNN8, dense-concat for DenseNet40).

Invariants:

* layer order is forward-pass order; consecutive specs are chainable
  (next ic == this oc, or == carried channels + oc for dense blocks) —
  relied on by ``mapped_net_apply`` and its tests — except in the
  networks ``GLUE`` names, whose branches re-read a module's input;
* ``stride``/``groups`` stay in the spec (MobileNet depthwise carries
  ``groups=ic``); nothing is pre-lowered, so every algorithm sees the
  layer the paper's tables describe;
* ``NETWORKS`` maps the benchmark names to zero-argument constructors
  (the benchmark scripts' registry); ``GLUE`` maps the names of the
  networks that are not chains to their layer graph, one ``GlueSpec`` a
  layer (:func:`with_glue` attaches it to a mapping).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

from .types import ConvLayerSpec, GlueSpec


def _c(name, i, k, ic, oc, stride=1, groups=1) -> ConvLayerSpec:
    return ConvLayerSpec(name=name, i_w=i, i_h=i, k_w=k, k_h=k,
                         ic=ic, oc=oc, stride=stride, groups=groups)


def cnn8() -> List[ConvLayerSpec]:
    """CNN8 from VW-SDK [20]; layer 1 excluded (not quantised/accelerated,
    §IV-B).  Rows match Table I verbatim."""
    return [
        _c("CNN8-2", 18, 3, 24, 32),
        _c("CNN8-3", 18, 3, 32, 32),
        _c("CNN8-4", 9, 3, 32, 64),
        _c("CNN8-5", 7, 3, 64, 64),
        _c("CNN8-6", 7, 3, 64, 64),
        _c("CNN8-7", 5, 5, 64, 256),
    ]


def inception() -> List[ConvLayerSpec]:
    """GoogLeNet Inception 5x5 branches (Table I rows)."""
    return [
        _c("Incep-3a", 28, 5, 16, 32),
        _c("Incep-3b", 28, 5, 32, 96),
        _c("Incep-4a", 14, 5, 16, 48),
        _c("Incep-4b", 14, 5, 24, 64),
        _c("Incep-4c", 14, 5, 24, 64),
        _c("Incep-4d", 14, 5, 32, 64),
        _c("Incep-4e", 14, 5, 32, 128),
        _c("Incep-5a", 7, 5, 32, 128),
    ]


#: GoogLeNet's nine Inception modules (Szegedy et al., "Going deeper with
#: convolutions", arXiv:1409.4842, Table 1): name, input channels,
#: feature-map size, then #1x1, #3x3 reduce, #3x3, #5x5 reduce, #5x5 and
#: pool proj output channels.
GOOGLENET_MODULES = (
    ("3a", 192, 28, 64, 96, 128, 16, 32, 32),
    ("3b", 256, 28, 128, 128, 192, 32, 96, 64),
    ("4a", 480, 14, 192, 96, 208, 16, 48, 64),
    ("4b", 512, 14, 160, 112, 224, 24, 64, 64),
    ("4c", 512, 14, 128, 128, 256, 24, 64, 64),
    ("4d", 512, 14, 112, 144, 288, 32, 64, 64),
    ("4e", 528, 14, 256, 160, 320, 32, 128, 128),
    ("5a", 832, 7, 256, 160, 320, 32, 128, 128),
    ("5b", 832, 7, 384, 192, 384, 48, 128, 128),
)

#: Modules a 3x3/2 max-pool (ceil mode: 28 -> 14 -> 7) precedes.
GOOGLENET_STAGE_POOLS = ("4a", "5a")


def inception_modules(modules, stage_pools=()
                      ) -> Tuple[List[ConvLayerSpec], Tuple[GlueSpec, ...]]:
    """``(layers, glue)`` of a stack of Inception modules, each given as
    in :data:`GOOGLENET_MODULES`; a 3x3/2 max-pool precedes the modules
    named in ``stage_pools``.  Six convs a module, in the order 1x1, 3x3
    reduce, 3x3, 5x5 reduce, 5x5, pool proj (sizes padded: +2 for 3x3,
    +4 for 5x5).  In the glue the 1x1 saves the module input and opens
    the branches; each branch hands the module input on, so the two
    reduces and the pool proj read it (the pool proj behind a 3x3/1
    max-pool), and the join concatenates 1x1, 3x3, 5x5 and pool proj;
    ReLU after every conv."""
    layers: List[ConvLayerSpec] = []
    glue: List[GlueSpec] = []
    for name, ic, size, c1, r3, c3, r5, c5, pp in modules:
        p = f"G{name}"
        layers += [_c(f"{p}-1x1", size, 1, ic, c1),
                   _c(f"{p}-3x3r", size, 1, ic, r3),
                   _c(f"{p}-3x3", size + 2, 3, r3, c3),
                   _c(f"{p}-5x5r", size, 1, ic, r5),
                   _c(f"{p}-5x5", size + 4, 5, r5, c5),
                   _c(f"{p}-pool", size, 1, ic, pp)]
        stage = "max3s2" if name in stage_pools else "none"
        glue += [GlueSpec(kind="branch", act="relu", save=True, pool=stage),
                 GlueSpec(act="relu"),
                 GlueSpec(kind="branch", act="relu"),
                 GlueSpec(act="relu"),
                 GlueSpec(kind="branch", act="relu"),
                 GlueSpec(kind="join", act="relu", pool="max3s1")]
    return layers, tuple(glue)


def googlenet() -> List[ConvLayerSpec]:
    """GoogLeNet's nine Inception modules whole (:func:`inception_modules`
    of :data:`GOOGLENET_MODULES`); the stem, the average pool and the
    classifiers are left out.  Not a chain: serve it with its glue,
    :func:`googlenet_glue`."""
    return inception_modules(GOOGLENET_MODULES, GOOGLENET_STAGE_POOLS)[0]


def googlenet_glue() -> Tuple[GlueSpec, ...]:
    """The layer graph of :func:`googlenet`, one ``GlueSpec`` a layer."""
    return inception_modules(GOOGLENET_MODULES, GOOGLENET_STAGE_POOLS)[1]


def densenet40(growth: int = 12, init_ch: int = 16) -> List[ConvLayerSpec]:
    """DenseNet-40 (3 dense blocks x 12 layers, growth k=12, no
    bottleneck/compression — the original DenseNet(L=40,k=12) [33]).

    3x3 convs inside blocks (pad 1 => IFM+2); 1x1 transition convs between
    blocks.  CIFAR geometry: blocks at 32/16/8 spatial.
    """
    layers: List[ConvLayerSpec] = []
    ch = init_ch
    size = 32
    for b in range(3):
        for li in range(12):
            layers.append(_c(f"DN40-b{b+1}l{li+1}", size + 2, 3, ch, growth))
            ch += growth
        if b < 2:
            layers.append(_c(f"DN40-t{b+1}", size, 1, ch, ch))
            size //= 2
    return layers


def mobilenet(width: int = 32) -> List[ConvLayerSpec]:
    """MobileNetV1 depthwise-separable stack at CIFAR geometry (§IV-C3:
    'mixture of depthwise and pointwise layers limits cross-channel reuse').

    Depthwise layers carry groups=IC (each group is a 1-channel conv);
    pointwise layers are 1x1.  Stride-2 layers keep stride in the spec.
    """
    cfg = [  # (dw stride, out channels) per separable block
        (1, 64), (2, 128), (1, 128), (2, 256), (1, 256),
        (2, 512), (1, 512), (1, 512),
    ]
    layers: List[ConvLayerSpec] = []
    size, ch = width, 32
    for i, (s, oc) in enumerate(cfg):
        layers.append(_c(f"MBN-dw{i+1}", size + 2, 3, ch, ch,
                         stride=s, groups=ch))
        size = size // s
        layers.append(_c(f"MBN-pw{i+1}", size, 1, ch, oc))
        ch = oc
    return layers


NETWORKS = {
    "cnn8": cnn8,
    "inception": inception,
    "densenet40": densenet40,
    "mobilenet": mobilenet,
    "googlenet": googlenet,
}

GLUE = {"googlenet": googlenet_glue}


def with_glue(name: str, mapping):
    """``mapping`` of the network ``name`` with its layer graph (its
    first ``len(mapping.layers)`` glue entries); unchanged for the
    networks whose chain or concat glue ``compile_plan`` infers."""
    if name not in GLUE:
        return mapping
    return dataclasses.replace(mapping,
                               glue=GLUE[name]()[:len(mapping.layers)])
