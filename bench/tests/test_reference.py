"""The plain reference against an explicit NumPy loop, on the CPU."""
import numpy as np
import pytest

import reference

#: A chain with every kind of glue the served chains have: grouped
#: convolutions, a pruned channel, two dense concats and a max-pool.
TINY = {
    "name": "tiny",
    "input": [4, 10, 10],
    "layers": [
        {"name": "T1", "size": 10, "kernel": 3, "ic": 4, "oc": 4,
         "stride": 1, "groups": 2, "pruned": []},
        {"name": "T2", "size": 10, "kernel": 3, "ic": 8, "oc": 4,
         "stride": 1, "groups": 2, "pruned": [[1, 1]]},
        {"name": "T3", "size": 4, "kernel": 3, "ic": 12, "oc": 8,
         "stride": 1, "groups": 1, "pruned": []},
    ],
}


def np_conv(x, w, groups):
    b, c, h, _ = x.shape
    k, o = w.shape[0], w.shape[3]
    cg, og, oh = c // groups, o // groups, h - k + 1
    y = np.zeros((b, o, oh, oh))
    for g in range(groups):
        xs = x[:, g * cg:(g + 1) * cg]
        for i in range(k):
            for j in range(k):
                y[:, g * og:(g + 1) * og] += np.einsum(
                    "bchw,co->bohw", xs[:, :, i:i + oh, j:j + oh],
                    w[i, j, :, g * og:(g + 1) * og])
    return y


def np_fit(x, size):
    while x.shape[2] >= 2 * size:
        h = x.shape[2] // 2 * 2
        x = x[:, :, :h, :h]
        b, c = x.shape[:2]
        x = x.reshape(b, c, h // 2, 2, h // 2, 2).max(axis=(3, 5))
    d = size - x.shape[2]
    if d > 0:
        x = np.pad(x, ((0, 0), (0, 0), (d // 2, d - d // 2),
                       (d // 2, d - d // 2)))
    elif d < 0:
        lo = -d // 2
        x = x[:, :, lo:lo + size, lo:lo + size]
    return x


def np_chain(cfg, kernels, x):
    x = np.asarray(x, np.float64)
    layers = cfg["layers"]
    for i, (lay, w) in enumerate(zip(layers, kernels)):
        xp = np_fit(x, lay["size"])
        y = np_conv(xp, np.asarray(w, np.float64), lay["groups"])
        if i + 1 == len(layers):
            return y
        if layers[i + 1]["ic"] == lay["oc"]:
            x = y
        else:
            o = (xp.shape[2] - y.shape[2]) // 2
            x = np.concatenate(
                [xp[:, :, o:o + y.shape[2], o:o + y.shape[3]], y], axis=1)


def test_forward_matches_numpy_loops():
    kernels, pool = reference.make_data(TINY, 6, seed=3)
    x = np.asarray(pool)
    y = np.asarray(reference.forward(TINY, kernels, pool))
    want = np_chain(TINY, kernels, x)
    assert y.shape == (6, 8, 2, 2)
    assert reference.rel_err(y, want) < 1e-5


def test_forward_blocks_pads_the_last_block():
    kernels, pool = reference.make_data(TINY, 5, seed=3)
    rows = np.asarray(pool)
    whole = np.asarray(reference.forward(TINY, kernels, pool))
    blocks = reference.forward_blocks(TINY, kernels, rows, block=2)
    assert blocks.shape == whole.shape
    assert reference.rel_err(blocks, whole) < 1e-6


def test_make_data_is_the_seeds_and_zeroes_pruned_channels():
    big = 2**40 + 3
    k1, p1 = reference.make_data(TINY, 4, seed=big)
    k2, p2 = reference.make_data(TINY, 4, seed=big)
    k3, _ = reference.make_data(TINY, 4, seed=big + 2**32)
    assert all(np.array_equal(a, b) for a, b in zip(k1, k2))
    assert np.array_equal(p1, p2)
    assert not np.array_equal(k1[0], k3[0])     # the high bits count
    assert np.all(np.asarray(k1[1])[:, :, 1, :] == 0)
    assert np.all(np.asarray(k1[1])[:, :, 0, :] != 0)
    assert [tuple(k.shape) for k in k1] == [(3, 3, 2, 4), (3, 3, 4, 4),
                                            (3, 3, 12, 8)]


def test_useful_flops_count_kept_channels_of_a_group():
    # T1: 8x8 out, 4 oc, 9 taps, 2 channels a group; T2 keeps 3 of 4;
    # T3: 2x2 out, 8 oc, 12 channels
    want = 2 * (64 * 4 * 9 * 2 + 64 * 4 * 9 * 3 + 4 * 8 * 9 * 12)
    assert reference.useful_flops_per_image(TINY) == want


@pytest.mark.parametrize("operands,lo", [("bfloat16", 1e-4),
                                         ("int8", 1e-2)])
def test_control_operands_move_the_result(operands, lo):
    kernels, pool = reference.make_data(TINY, 8, seed=5)
    ref = np.asarray(reference.forward(TINY, kernels, pool))
    low = np.asarray(reference.forward(TINY, kernels, pool,
                                       operands=operands))
    assert lo < reference.rel_err(low, ref) < 0.5


def test_rel_err_refuses_non_finite_and_misshapen():
    a = np.ones((2, 3))
    assert reference.rel_err(a, a) == 0.0
    assert reference.rel_err(a * np.nan, a) == float("inf")
    assert reference.rel_err(a[:1], a) == float("inf")
