"""The dense family's readings on the tests' small configuration, held
to literals: the weights and an adapter made from two seeds (a float64
sum and sum of squares of every leaf), the reference's served-token gaps
with and without the fp8 control, its first two train steps, and the
work counts.  A change to how the benchmark makes weights, runs its
reference or counts operations shows here: weights, adapters and counts
bit for bit, the reference's readings to its float32 round-off."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import spec

TINY = json.loads((spec.BENCH_DIR / "tests" / "data" / "configs"
                   / "tiny.json").read_text())
SEEDS = (7, 2**31 + 3)
PROMPT = np.array([3, 141, 59, 26, 5, 35, 89, 79, 32], np.int32)
SERVED_TOKENS = np.array([38, 46, 2, 64, 33, 83, 27], np.int32)
PAD_TO = 24
# XLA's CPU products sum in an order that depends on its thread pool:
# the reference's readings agree to about 5e-6 between thread counts,
# and bit for bit within one
REF = {"rel": 2e-5, "abs": 1e-6}
OPT = {"lr": 0.003, "b1": 0.9, "b2": 0.999, "eps": 1e-08, "clip_norm": 1.0}


def _batches():
    rng = np.random.default_rng(11)
    out = []
    for _ in range(2):
        mask = np.ones((2, 12), np.float32)
        mask[1, -3:] = 0.0
        out.append({"tokens": rng.integers(0, 256, (2, 12), dtype=np.int32),
                    "labels": rng.integers(0, 256, (2, 12), dtype=np.int32),
                    "mask": mask})
    return out


def _leaves(tree):
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return [(".".join(k.key for k in p), np.asarray(x, np.float64))
            for p, x in flat]


def moments(tree):
    """Per leaf: (sum, sum of squares), in float64."""
    return {k: (float(x.sum()), float((x * x).sum()))
            for k, x in _leaves(tree)}


def norms(tree):
    return {k: float(np.sqrt((x * x).sum())) for k, x in _leaves(tree)}


def served_reading(fam, seed, quant):
    params = fam.make_params(TINY, seed)
    adapter = fam.make_adapter(TINY, seed, 1)
    gap, ctrl = fam.served_gaps(params, adapter, PROMPT, SERVED_TOKENS,
                                PAD_TO, TINY, quant)
    return {"gap": [float(v) for v in gap], "ctrl": [float(v) for v in ctrl]}


def train_reading(fam, seed, quant):
    params = fam.make_params(TINY, seed)
    lora0 = fam.make_adapter(TINY, seed, 0)
    losses, grad, adapter = fam.train_steps(params, lora0, _batches(), TINY,
                                            OPT, quant)
    change = jax.tree.map(lambda a, b: a - b, adapter, lora0)
    return {"losses": [float(v) for v in losses], "grad": norms(grad),
            "change": norms(change)}


def count_reading(fam):
    return {"prefill": fam.prefill_ops(TINY, [3, 17]),
            "decode": fam.decode_ops(TINY, [5, 40]),
            "train": fam.train_ops(TINY, 2, 12),
            "paged": fam.paged_attention(TINY, [3, 5, 40])}


# the readings of the dense decoder's weights, reference and counts as
# the benchmark first defined them; a change here changes the yardstick
PARAMS = {
    7: {
        "blocks.attn.bk": (-0.8853302001953125, 0.534572625765577),
        "blocks.attn.bq": (-0.16454315185546875, 1.2499381846864708),
        "blocks.attn.bv": (-0.4600372314453125, 0.6529199706856161),
        "blocks.attn.wk": (-1.5432615280151367, 64.72793083856459),
        "blocks.attn.wo": (18.915642827749252, 125.31501548741494),
        "blocks.attn.wq": (1.8764159977436066, 126.6293409188664),
        "blocks.attn.wv": (-3.2183908820152283, 64.36587374911916),
        "blocks.ln1": (129.66015625, 132.6632537841797),
        "blocks.ln2": (129.0546875, 131.27490234375),
        "blocks.mlp.wd": (23.855150401592255, 126.6830448275693),
        "blocks.mlp.wg": (-8.605080097913742, 253.8292124842987),
        "blocks.mlp.wu": (-7.009000219404697, 258.07906955525095),
        "embed": (-42.299762815237045, 255.95509489990337),
        "final_norm": (64.28125, 65.17837524414062),
        "lm_head": (-42.299762815237045, 255.95509489990337),
    },
    2147483651: {
        "blocks.attn.bk": (-0.1241302490234375, 0.5931654938030988),
        "blocks.attn.bq": (1.2304210662841797, 1.4130346164492948),
        "blocks.attn.bv": (-1.0350322723388672, 0.7398167755491158),
        "blocks.attn.wk": (4.450289487838745, 64.34751304827097),
        "blocks.attn.wo": (-2.181011199951172, 127.52078027805317),
        "blocks.attn.wq": (-22.166454672813416, 127.40048937141724),
        "blocks.attn.wv": (-2.738504469394684, 63.54227261236937),
        "blocks.ln1": (128.7734375, 131.0562744140625),
        "blocks.ln2": (129.30859375, 131.95518493652344),
        "blocks.mlp.wd": (23.465525336563587, 129.45661061856148),
        "blocks.mlp.wg": (3.123822510242462, 259.475645268861),
        "blocks.mlp.wu": (-23.320191502571106, 256.5325322648743),
        "embed": (0.43721675872802734, 251.80425026303442),
        "final_norm": (63.8046875, 64.38916015625),
        "lm_head": (0.43721675872802734, 251.80425026303442),
    },
}
ADAPTER = {
    7: {
        "k.a": (-3.7821478023579402, 7.414964442534513),
        "k.b": (-0.10654708102083532, 0.6498829135023472),
        "o.a": (-2.5920930845313706, 7.064619497680985),
        "o.b": (0.5713094813509088, 1.3465246221254037),
        "q.a": (-3.57659655821044, 7.972600525895266),
        "q.b": (-0.5159650816203794, 1.1358856639933745),
        "v.a": (-6.087349216490111, 7.745960849641594),
        "v.b": (-0.1593706048661261, 0.666943896314711),
    },
    2147483651: {
        "k.a": (0.9877410453627817, 8.181151670312921),
        "k.b": (-0.812019903503824, 0.7108397321275111),
        "o.a": (4.49797169951853, 7.8725513219688885),
        "o.b": (-1.8007338714087382, 1.3275651578522003),
        "q.a": (-1.0822004167712294, 8.532868024844783),
        "q.b": (-0.6855374752558419, 1.235933261607625),
        "v.a": (0.06001227447995916, 7.636401198021076),
        "v.b": (-0.03765280880725186, 0.6309700703853036),
    },
}
SERVED = {
    7: {
        "gap": [
            2.541193962097168, 3.3800787925720215, 5.131467342376709,
            4.33613920211792, 2.0923428535461426, 3.3185606002807617,
            1.4238953590393066],
        "ctrl": [
            0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.2656586170196533],
    },
    2147483651: {
        "gap": [
            3.9402027130126953, 4.27404260635376, 3.982041358947754,
            2.0786633491516113, 3.087573289871216, 2.303096294403076,
            2.152465581893921],
        "ctrl": [
            0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
    },
}
TRAIN = {
    (2147483651, None): {
        "losses": [
            5.912993907928467, 5.700192928314209],
        "grad": {
            "k.a": 0.1258528537648194,
            "k.b": 0.23192079178279287,
            "o.a": 0.2345605383250597,
            "o.b": 0.5545908827323495,
            "q.a": 0.10577491243958724,
            "q.b": 0.21914283037507792,
            "v.a": 0.17651378921759675,
            "v.b": 0.6909524129540453,
        },
        "change": {
            "k.a": 0.10505615730707915,
            "k.b": 0.07333099054340626,
            "o.a": 0.1032039836868364,
            "o.b": 0.10365036315589211,
            "q.a": 0.10271360311758947,
            "q.b": 0.10125332075161739,
            "v.a": 0.10074505087591057,
            "v.b": 0.07218064469564325,
        },
    },
    (2147483651, "fp8"): {
        "losses": [
            5.910470962524414, 5.67326021194458],
        "grad": {
            "k.a": 0.11844010306798665,
            "k.b": 0.2346624863368124,
            "o.a": 0.23846572673823307,
            "o.b": 0.5559919144768468,
            "q.a": 0.10804421373016553,
            "q.b": 0.20754220901849182,
            "v.a": 0.18104352439625881,
            "v.b": 0.6909331568106121,
        },
        "change": {
            "k.a": 0.10567605585831993,
            "k.b": 0.07387108314397195,
            "o.a": 0.1038093003496235,
            "o.b": 0.10413220335271973,
            "q.a": 0.10281224961051616,
            "q.b": 0.10175354510863664,
            "v.a": 0.10217218862235684,
            "v.b": 0.07212797336175876,
        },
    },
    (7, None): {
        "losses": [
            6.068375587463379, 5.948106288909912],
        "grad": {
            "k.a": 0.13860595048189575,
            "k.b": 0.33480670833971504,
            "o.a": 0.28483200879653625,
            "o.b": 0.530852317803935,
            "q.a": 0.1521234875868238,
            "q.b": 0.3790683141748858,
            "v.a": 0.21408917188791327,
            "v.b": 0.5413786638762235,
        },
        "change": {
            "k.a": 0.10468665682759544,
            "k.b": 0.07557034127495009,
            "o.a": 0.10927241419167258,
            "o.b": 0.10298123462691935,
            "q.a": 0.10540513820118316,
            "q.b": 0.10595639412955961,
            "v.a": 0.10336443587163137,
            "v.b": 0.07110494897810618,
        },
    },
    (7, "fp8"): {
        "losses": [
            6.058603763580322, 5.963191986083984],
        "grad": {
            "k.a": 0.15316789098649583,
            "k.b": 0.3315874671909601,
            "o.a": 0.29402301479940934,
            "o.b": 0.530662500557679,
            "q.a": 0.15563617982173467,
            "q.b": 0.3767092468386661,
            "v.a": 0.21860853757630058,
            "v.b": 0.5334931452302106,
        },
        "change": {
            "k.a": 0.10588170327130417,
            "k.b": 0.07285526376200713,
            "o.a": 0.10838302706292531,
            "o.b": 0.10309334643671705,
            "q.a": 0.10563531828173453,
            "q.b": 0.1052465247947112,
            "v.a": 0.10339908365236838,
            "v.b": 0.07149573742934828,
        },
    },
}
COUNTS = {"prefill": 3239424, "decode": 397824, "train": 9326592,
          "paged": {"ops": 24576, "bytes": 13824}}


@pytest.fixture
def fam():
    return spec.family(TINY)


@pytest.mark.parametrize("seed", SEEDS)
def test_weights_are_pinned(fam, seed):
    assert moments(fam.make_params(TINY, seed)) == PARAMS[seed]


@pytest.mark.parametrize("seed", SEEDS)
def test_adapter_is_pinned(fam, seed):
    assert moments(fam.make_adapter(TINY, seed, 1)) == ADAPTER[seed]


@pytest.mark.parametrize("seed", SEEDS)
def test_served_gaps_are_pinned(fam, seed):
    plain = served_reading(fam, seed, None)
    ctrl = served_reading(fam, seed, "fp8")
    want = SERVED[seed]
    assert plain["gap"] == pytest.approx(want["gap"], **REF)
    assert plain["ctrl"] == [0.0] * len(SERVED_TOKENS)
    assert ctrl["gap"] == pytest.approx(want["gap"], **REF)
    assert ctrl["ctrl"] == pytest.approx(want["ctrl"], **REF)


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_train_steps_are_pinned(fam, seed, quant):
    got = train_reading(fam, seed, quant)
    want = TRAIN[(seed, quant)]
    for part in ("losses", "grad", "change"):
        assert got[part] == pytest.approx(want[part], **REF), part


def test_counts_are_pinned(fam):
    assert count_reading(fam) == COUNTS
