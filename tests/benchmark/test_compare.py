"""The arithmetic of the training check: gaps of norms by the worst and
the median leaf, the leaves left out, and which numbers a cell's limits
file holds to a limit."""
import numpy as np
import pytest

from benchmarks.drivers import fit
from benchmarks.lib import leaves
from benchmarks.references import optim


def _reference():
    grad = {"a_weight": 1.0, "b_weight": 2.0, "c_bias": 4.0, "d_bias": 1e-5}
    change = {"a_weight": 0.1, "b_weight": 0.2, "c_bias": 0.4,
              "d_bias": 0.3}
    return {"losses": [10.0, 9.0, 8.0], "grad": grad, "change": change}


def test_gaps_are_of_norms_against_the_leaf_or_the_median_leaf():
    ref = _reference()
    prog = {"losses": [10.0, 9.09, 8.0],
            "grad": {"a_weight": 1.3, "b_weight": 2.0, "c_bias": 4.4,
                     "d_bias": 2e-5},
            "change": {"a_weight": 0.1, "b_weight": 0.25, "c_bias": 0.4,
                       "d_bias": 0.9}}
    limits = {"loss_gap": 0.02, "grad_gap": 0.2, "change_gap": 0.3}
    out = fit.compare(prog, ref, limits)
    numbers = {n: (v, lim) for n, v, lim in out["numbers"]}
    assert list(numbers) == ["loss_gap", "grad_gap", "change_gap"]
    assert numbers["loss_gap"][0] == pytest.approx(0.01)
    # median of the reference's gradient norms is 1.5: a_weight's gap of
    # 0.3 is measured against 1.5, c_bias's 0.4 against its own 4.0, and
    # d_bias, all but zero, against the median
    assert numbers["grad_gap"][0] == pytest.approx(0.3 / 1.5)
    # d_bias's gradient is under a thousandth of the median leaf's: it
    # moves by round-off alone and is left out of the change
    assert out["notes"]["leaves_left_out"] == ["d_bias"]
    assert numbers["change_gap"][0] == pytest.approx(0.05 / 0.2)
    others = out["notes"]["read_not_compared"]
    assert others["loss1_gap"] == 0.0
    assert others["grad_median_gap"] == pytest.approx(
        np.median([0.2, 0.0, 0.1, 1e-5 / 1.5]))
    assert others["change_median_gap"] == pytest.approx(0.0)


def test_the_limits_file_chooses_the_numbers_and_a_stranger_is_refused():
    ref = _reference()
    out = fit.compare(ref, ref, {"loss1_gap": 0.01, "grad_median_gap": 0.1})
    assert [(n, v) for n, v, _l in out["numbers"]] \
        == [("loss1_gap", 0.0), ("grad_median_gap", 0.0)]
    with pytest.raises(KeyError, match="no_such_gap"):
        fit.compare(ref, ref, {"no_such_gap": 1.0})


def test_the_worst_leaf_is_read_over_the_leaves_the_mix_names():
    """The traffic file may name the leaves over which the worst leaf is
    read; the others still count in the median."""
    ref = _reference()
    prog = {"losses": ref["losses"],
            "grad": {"a_weight": 1.03, "b_weight": 2.0, "c_bias": 5.2,
                     "d_bias": 1e-5},
            "change": {"a_weight": 0.1, "b_weight": 0.21, "c_bias": 0.8,
                       "d_bias": 0.3}}
    limits = {"grad_gap": 0.1, "change_gap": 0.1, "grad_median_gap": 0.1}
    rule = {"names": "^(a|b)_"}
    numbers = {n: v for n, v, _l in fit.compare(prog, ref, limits)["numbers"]}
    assert numbers["grad_gap"] == pytest.approx(1.2 / 4.0)
    assert numbers["change_gap"] == pytest.approx(1.0)
    kept = fit.compare(prog, ref, limits, rule)
    numbers = {n: v for n, v, _l in kept["numbers"]}
    assert kept["notes"]["leaves_in_the_worst"] == 2
    assert numbers["grad_gap"] == pytest.approx(0.03 / 1.5)
    assert numbers["change_gap"] == pytest.approx(0.01 / 0.2)
    assert numbers["grad_median_gap"] == pytest.approx(
        np.median([0.02, 0.0, 0.3, 0.0]))
    # a fault in a leaf that is named still fails, and a rule that names
    # no leaf passes nothing
    prog["grad"]["b_weight"] = 1.0
    assert fit.compare(prog, ref, limits, rule)["numbers"][0][1] \
        == pytest.approx(0.5)
    assert fit.compare(prog, ref, limits, {"names": "^z_"})["numbers"][0][1] \
        == float("inf")


def test_an_unmoved_leaf_reads_one():
    ref = _reference()
    prog = dict(ref, change={k: 0.0 for k in ref["change"]})
    out = fit.compare(prog, ref, {"change_gap": 0.5})
    assert out["numbers"][0][1] == pytest.approx(1.0)


def test_first_gradient_from_the_optimizer_state():
    import jax.numpy as jnp
    g = jnp.asarray([1.0, -2.0, 3.0])
    adam = {"name": "adam", "beta1": 0.9, "beta2": 0.95}
    tree, factor = optim.first_grad(adam, {"w": ((1 - 0.9) * g, jnp.zeros(3))})
    assert np.allclose(factor * tree["w"], g)
    sgd = {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9}
    tree, factor = optim.first_grad(sgd, {"w": -0.1 * g})
    assert np.allclose(factor * tree["w"], g)


def test_weights_repeat_from_the_seed_and_the_change_finds_them_again():
    specs = {"x_weight": ((4, 6), 0.0, 0.5), "x_gamma": ((6,), 1.0, 0.0),
             "qkv_bias": ((6,), 0.0, 0.1)}

    def parts(name):
        if name != "qkv_bias":
            return [("", None)]
        return [(".q", slice(0, 2)), (".k", slice(2, 4)), (".v", slice(4, 6))]
    seed = 2 ** 31 + 77
    a, b = leaves.make(specs, seed), leaves.make(specs, seed)
    other = leaves.make(specs, seed + 1)
    for k in specs:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["x_weight"], other["x_weight"])
    assert np.array_equal(a["x_gamma"], np.ones(6, np.float32))
    moved = {k: v + 1.0 for k, v in a.items()}
    change = {k: float(v) for k, v in
              leaves.change_norms(specs, seed, moved, parts).items()}
    assert change["x_weight"] == pytest.approx(24 ** 0.5, rel=1e-5)
    assert change["qkv_bias.k"] == pytest.approx(2 ** 0.5, rel=1e-5)
    norms = leaves.norms({"qkv_bias": np.arange(6, dtype=np.float32)}, parts)
    assert float(norms["qkv_bias.v"]) == pytest.approx((16 + 25) ** 0.5)
    assert leaves.weight_decayed("x_weight") and leaves.weight_decayed(
        "x_gamma") and not leaves.weight_decayed("qkv_bias")
