"""A configuration cut to one chip's share of its published model.

``spec.check_cut`` keeps a cut to a model that its chips cannot hold, to
the depth, the vocabulary and the keys that the family's ``cuts`` adds,
each between its floor and its published value; ``harness.program_config``
applies the cut to the program's one published registry entry and checks
every other size against it.  On the CPU, with no program compile:
granite-20b (40.6 GB of weights) cut to one stage of a four-stage
pipeline on one chip, minitron-4b (8.4 GB), which one chip holds whole,
and toy families registered as ``families/toyexp.py`` would be.
"""
import copy
import dataclasses
import json
import sys
import types

import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import counts, harness, spec, weights
from benchmarks.tpu.weights import Leaf

with open(h.REPO + "/benchmarks/tpu/configs/granite-20b.tp4.json") as f:
    GRANITE = json.load(f)
with open(h.REPO + "/benchmarks/tpu/configs/minitron-4b.json") as f:
    MINITRON = json.load(f)

DEPLOYMENT = ("one of four pipeline stages on four TPU v5e chips, 13 of the "
              "52 layers, with an eighth of the embedding and head, as "
              "eight chips split the vocabulary")


def _cut(**over):
    """granite-20b on one chip: 13 of its 52 layers and 6,144 of its
    49,152 tokens."""
    c = copy.deepcopy(GRANITE)
    c.update(name="granite-20b.pp4", chips=1, mesh={"data": 1, "model": 1},
             n_layers=13, vocab=6144, reduced=["n_layers", "vocab"],
             published={"n_layers": 52, "vocab": 49152},
             deployment=DEPLOYMENT)
    c.update(over)
    return c


def test_the_cut_is_applied_to_the_published_entry():
    from repro.models.registry import get_arch
    c = _cut()
    spec.check_cut(c)
    want = dataclasses.asdict(get_arch("granite-20b"))
    want.update(n_layers=13, vocab=6144)
    assert dataclasses.asdict(harness.program_config(c)) == want


def test_an_uncut_configuration_runs_the_published_entry_itself():
    from repro.models.registry import get_arch
    spec.check_cut(MINITRON)
    assert harness.program_config(MINITRON) is get_arch("minitron-4b")


def test_the_placement_takes_the_cut_programs_layout():
    from repro.models.registry import abstract_params
    c = _cut()
    ap = abstract_params(harness.program_config(c))
    assert ap["embed"].shape == (6144, 6144)
    assert ap["layers"]["mlp"]["w_in"].shape == (13, 6144, 24576)
    assert callable(weights.placer(c, ap, None))


def test_weight_bytes_of_the_cut_by_hand():
    # batch 64, bfloat16.  A layer: two norms 2*6144, wq and wo
    # 2*6144*6144, wk and wv 2*6144*128 (one KV head), w_in and w_out
    # 2*6144*24576: 379,072,512 parameters, 13 layers 4,927,942,656.
    # Outside them: the embedding's looked-up rows 64*6144, the final
    # norm 6144, the head 6144*6144: 38,148,096.  Two bytes each.
    layer = 2 * 6144 + 2 * 6144 * 6144 + 2 * 6144 * 128 + 2 * 6144 * 24576
    assert layer == 379_072_512
    hand = 2 * (13 * layer + 64 * 6144 + 6144 + 6144 * 6144)
    assert hand == 9_932_181_504
    assert counts.weight_bytes(_cut(), 64) == hand


def test_the_published_models_bytes_by_hand():
    # all 52 layers, every row of the 49,152-token embedding and the head
    hand = 2 * (52 * 379_072_512 + 2 * 49152 * 6144 + 6144)
    assert hand == 40_631_513_088
    assert spec.published_weight_bytes(_cut()) == hand


def _minitron_cut(**over):
    """minitron-4b cut to 4 layers and 32,000 tokens: a cut that one
    chip, which holds the whole model, does not force."""
    c = copy.deepcopy(MINITRON)
    c.update(n_layers=4, vocab=32000, reduced=["n_layers", "vocab"],
             published={"n_layers": 32, "vocab": 256000},
             deployment="one stage of a pipeline over eight chips")
    c.update(over)
    return c


#: (case, configuration, what the refusal names)
REFUSED = [
    ("width", lambda: _cut(reduced=["n_layers", "vocab", "d_model"],
                           published={"n_layers": 52, "vocab": 49152,
                                      "d_model": 6144}),
     "'d_model' is a width"),
    ("vocab_under_its_floor", lambda: _cut(vocab=6143),
     "'vocab' = 6143 is under its floor 6144"),
    ("depth_under_its_floor", lambda: _cut(n_layers=3),
     "'n_layers' = 3 is under its floor 4"),
    ("above_published", lambda: _cut(n_layers=53),
     "'n_layers' = 53 is above its published 52"),
    ("cut_key_without_published", lambda: _cut(published={"n_layers": 52}),
     "'vocab' has no published value"),
    ("published_key_not_cut", lambda: _cut(reduced=["n_layers"],
                                           vocab=49152),
     "'vocab' is stated as cut but reduced does not name it"),
    ("cut_key_not_stated", lambda: _cut(
        reduced=["n_layers", "vocab", "n_experts"],
        published={"n_layers": 52, "vocab": 49152, "n_experts": 64}),
     "reduced names 'n_experts', which the configuration does not state"),
    ("no_deployment", lambda: _cut(deployment=""),
     "states no deployment"),
    ("not_a_count", lambda: _cut(published={"n_layers": "52",
                                            "vocab": 49152}),
     "'n_layers': a cut and its published value are counts"),
    ("a_model_its_chip_holds", _minitron_cut,
     "nothing forces the cut"),
    ("on_the_four_chips_that_hold_it", lambda: _cut(
        chips=4, mesh={"data": 1, "model": 4}),
     "nothing forces the cut"),
]


@pytest.mark.parametrize("make,names", [(m, n) for _, m, n in REFUSED],
                         ids=[case for case, _, _ in REFUSED])
def test_a_cut_out_of_bounds_is_refused(make, names):
    with pytest.raises(spec.SpecError) as e:
        spec.check_cut(make())
    assert names in str(e.value)


def test_a_published_value_unlike_the_programs_is_refused():
    c = _cut(published={"n_layers": 51, "vocab": 49152})
    spec.check_cut(c)
    with pytest.raises(ValueError, match=r"'n_layers': \(52, 51\)"):
        harness.program_config(c)


def test_a_cut_size_unlike_the_files_is_refused():
    # the program is cut to the file's 13 layers; a size the file states
    # otherwise is still held against it
    c = _cut(d_ff=12288)
    with pytest.raises(ValueError, match=r"'d_ff': \(24576, 12288\)"):
        harness.program_config(c)


# Toy MoE families too large for one chip: a dense prefix of 3 layers
# (``dense_layers``) before the MoE layers (``layers``), each of which
# holds ``n_experts_held`` of the experts, at DeepSeek-V3's widths.
TOY = {"family": "toyexp", "arch": "deepseek-v3-671b", "chips": 1,
       "dtype": "bfloat16", "d_model": 7168, "vocab": 129280,
       "n_layers": 7, "n_dense": 3, "n_experts_held": 8,
       "reduced": ["n_layers", "n_experts_held"],
       "published": {"n_layers": 61, "n_experts_held": 256},
       "deployment": "one pipeline stage of 7 layers, with 8 of the 256 "
                     "experts of each MoE layer, as 32 chips hold them"}


def _toy_family(cuts=None):
    mod = types.ModuleType("bench_family_toyexp")
    mod.SIZES = ()

    def leaves(c):
        d, e, dt = c["d_model"], c["n_experts_held"], c["dtype"]
        return [Leaf("dense_layers/mlp/w_in", (d, 18432), weights.dense, dt,
                     True),
                Leaf("dense_layers/mlp/w_out", (18432, d), weights.dense, dt,
                     True),
                Leaf("layers/experts/w_in", (e, d, 2048), weights.dense, dt,
                     True),
                Leaf("layers/experts/w_out", (e, 2048, d), weights.dense, dt,
                     True)]

    mod.leaves = leaves
    mod.stacks = lambda c: [("dense_layers", c["n_dense"]),
                            ("layers", c["n_layers"] - c["n_dense"])]
    if cuts is not None:
        mod.cuts = cuts
    return mod


@pytest.fixture
def toyexp(monkeypatch):
    def register(cuts=None):
        mod = _toy_family(cuts)
        monkeypatch.setitem(sys.modules, "bench_family_toyexp", mod)
        return mod
    return register


def _check(c, names):
    if names is None:
        spec.check_cut(c)
    else:
        with pytest.raises(spec.SpecError) as e:
            spec.check_cut(c)
        assert names in str(e.value)


@pytest.mark.parametrize("over,names", [
    ({"reduced": ["n_layers"], "published": {"n_layers": 61},
      "n_experts_held": 256}, None),
    ({"reduced": ["n_layers"], "published": {"n_layers": 61},
      "n_experts_held": 256, "n_layers": 6},
     "'n_layers' = 6 is under its floor 7"),
    ({}, "'n_experts_held' may not be cut"),
], ids=["prefix_and_four_layers", "depth_one_below", "experts_not_allowed"])
def test_the_depth_floor_follows_the_familys_stacks(toyexp, over, names):
    # no ``cuts``: the prefix of 3 dense layers and 4 MoE layers
    toyexp()
    _check(dict(TOY, **over), names)


@pytest.mark.parametrize("over,names", [
    ({}, None),
    ({"n_layers": 6}, "'n_layers' = 6 is under its floor 7"),
    ({"n_experts_held": 7}, "'n_experts_held' = 7 is under its floor 8"),
    ({"n_experts_held": 257}, "'n_experts_held' = 257 is above"),
], ids=["at_its_floors", "depth_one_below", "experts_one_below",
        "experts_above_published"])
def test_a_family_adds_a_key(toyexp, over, names):
    toyexp(lambda c: {"n_experts_held": 8})
    _check(dict(TOY, **over), names)


@pytest.mark.parametrize("over,names", [
    ({"n_layers": 8}, None),
    ({"n_layers": 7}, "'n_layers' = 7 is under its floor 8"),
], ids=["at_its_floor", "one_below"])
def test_a_family_raises_the_depth_floor(toyexp, over, names):
    # a whole period of the MoE stack, five layers, after the prefix
    toyexp(lambda c: {"n_experts_held": 8, "n_layers": c["n_dense"] + 5})
    _check(dict(TOY, **over), names)


def test_a_family_cannot_allow_a_width(toyexp):
    toyexp(lambda c: {"moe_d_ff": 256})
    c = dict(TOY, moe_d_ff=256, reduced=["moe_d_ff"],
             published={"moe_d_ff": 2048})
    with pytest.raises(spec.SpecError, match="'moe_d_ff' is a width"):
        spec.check_cut(c)


def test_a_key_neither_the_default_nor_the_family_allows_is_refused(
        toyexp):
    toyexp(lambda c: {"n_experts_held": 8})
    c = dict(TOY, n_group=4, reduced=["n_group"], published={"n_group": 8})
    with pytest.raises(spec.SpecError, match="'n_group' may not be cut"):
        spec.check_cut(c)


def test_a_cut_key_the_program_lacks_is_refused(toyexp):
    # the family allows the experts held; the program's published entry
    # has no such field, so there is nothing to cut
    toyexp(lambda c: {"n_experts_held": 8})
    spec.check_cut(TOY)
    with pytest.raises(ValueError, match=r"no field for the cut keys "
                                         r"\['n_experts_held'\]"):
        harness.program_config(TOY)
