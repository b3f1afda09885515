"""Zoo graphs build + arch activation-arena DMO plans."""
import collections

import pytest

from repro.configs import registry
from repro.core import zoo
from repro.core.activation_planner import plan_block
from repro.core.planner import plan_dmo, plan_original


@pytest.mark.parametrize("name", list(zoo.TABLE3_MODELS))
def test_zoo_builds_and_validates(name):
    g = zoo.TABLE3_MODELS[name][0]()
    g.validate()
    assert len(g.ops) >= 25
    assert g.peak_bytes_lower_bound() > 0


def test_mobilenet_originals_match_paper():
    for name in ("mobilenet_v1_1.0_224", "mobilenet_v1_0.25_224",
                 "mobilenet_v2_0.35_224", "mobilenet_v2_1.0_224",
                 "mobilenet_v1_0.25_128_8bit"):
        build, orig_kb, _ = zoo.TABLE3_MODELS[name]
        assert plan_original(build()).peak_bytes == orig_kb * 1024, name


def _params_and_macs(g):
    params = macs = 0
    for op in g.ops:
        if op.kind == "conv2d":
            kh, kw = op.params["kernel"]
            w = kh * kw * op.inputs[0].shape[-1] * op.output.shape[-1]
            params += w
            macs += w * op.output.shape[-3] * op.output.shape[-2]
        elif op.kind == "fully_connected":
            w = op.inputs[0].shape[-1] * op.output.shape[-1]
            params += w
            macs += w
    return params, macs


@pytest.mark.parametrize("repeats,ops,params,macs", [
    ((10, 20, 10), 335, 55_736_160, 13_155_794_016),
    ((1, 1, 1), 58, None, None),
])
def test_inception_resnet_v2_published_graph(repeats, ops, params, macs):
    """The published graph (Keras InceptionResNetV2, 299 px): 335 ops, of
    them 244 convs, 43 concats, 40 adds and 5 pools; 55.7 M weights and
    13.16 G multiply-adds. ``repeats`` cuts only the block counts."""
    g = zoo.inception_resnet_v2(299, 4, repeats)
    kinds = collections.Counter(op.kind for op in g.ops)
    assert len(g.ops) == ops
    n_blocks = sum(repeats)
    assert kinds["elementwise"] == n_blocks
    assert kinds["concat"] == n_blocks + 3
    assert kinds["pool"] == 5
    assert [t.shape for t in g.tensors if t.kind == "output"] == [(1000,)]
    if params is not None:
        assert _params_and_macs(g) == (params, macs)
        assert kinds["conv2d"] == 244


@pytest.mark.parametrize("arch", list(registry()))
def test_block_activation_dmo_saves(arch):
    cfg = registry()[arch]
    orig, dmo = plan_block(cfg, batch=1, seq=64)
    orig.validate()
    dmo.validate()
    assert dmo.peak_bytes <= orig.peak_bytes
    # every family has elementwise chains: DMO must find real savings
    assert dmo.peak_bytes < orig.peak_bytes, arch


def test_operation_splitting_paper_example():
    """§II.A: splitting the (conv, dwconv) pair of MobileNet v1 0.25 128
    cuts the peak from 96 KB to <=66 KB at a bounded recompute cost."""
    from repro.core.splitting import auto_split, split_pair
    g = zoo.mobilenet_v1(0.25, 128, 1, external_input=True)
    assert plan_original(g).peak_bytes == 96 * 1024
    ng, rc = split_pair(g, 2, 4)
    ng.validate()
    assert plan_original(ng).peak_bytes <= 66 * 1024
    assert 0 < rc <= 6144  # paper: 6144 (coarser halo convention)
    ag, arc, log = auto_split(g)
    assert plan_original(ag).peak_bytes <= 66 * 1024
    assert log, "auto_split must find the paper's pair"


def test_operation_removal_squeezenet():
    """§II.C: concat elision turns branch outputs into views; the
    concat-dominated fire-module footprint shrinks and plans stay safe."""
    from repro.core.removal import remove_concats
    from repro.core.zoo import squeezenet
    g = squeezenet()
    g2 = remove_concats(g)
    assert len(g2.ops) == len(g.ops) - 8          # 8 fire concats elided
    g2.validate()
    p = plan_dmo(g2, method="algorithmic")
    p.validate()
    assert p.peak_bytes <= plan_original(g).peak_bytes
