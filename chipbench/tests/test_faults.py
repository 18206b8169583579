"""A run with the timed path broken underneath comes out not correct,
once for each fault these cells can have. (No cell exchanges anything
between chips: the tenant-sharded tick has no collective, so that fault
has no place to occur.)"""
import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests import tiny


def _stale(real):
    """The tick returns its state unchanged."""
    def observe_many(state, xs, ys, taus, active=None):
        _, p = real(jax.tree_util.tree_map(jnp.copy, state), xs, ys, taus,
                    active)
        return state, p
    return observe_many


def _half(real):
    """Half of the tenants' ticks are left out."""
    def observe_many(state, xs, ys, taus, active=None):
        T, S = ys.shape
        keep = jnp.broadcast_to(jnp.arange(S) < S // 2, (T, S))
        return real(state, xs, ys, taus, keep)
    return observe_many


def _tick_answer(real):
    """One tenant's served p-values are altered where they are made."""
    def observe_many(state, xs, ys, taus, active=None):
        state, p = real(state, xs, ys, taus, active)
        return state, p.at[:, 1].set(1.0 - p[:, 1])
    return observe_many


@pytest.mark.parametrize("measure", ["knn_class", "knn_reg"])
@pytest.mark.parametrize("fault", [_stale, _half, _tick_answer])
def test_broken_tick_is_not_correct(measure, fault):
    cell = tiny.cell(measure)
    eng = cell.engine()
    eng.observe_many = fault(eng.observe_many)
    assert not harness.run(cell, 3, 1.0)["correct"]


@pytest.mark.parametrize("measure,mix", [("knn_class", "ticks"),
                                         ("knn_reg", "reads")])
def test_broken_read_answer_is_not_correct(measure, mix, monkeypatch):
    """One tenant's read answers are altered where they are made."""
    cell = tiny.cell(measure, mix)
    real = cell.measure.read

    def read(eng, state, Xq, m):
        return real(eng, state, Xq, m).at[2].add(0.05)

    monkeypatch.setattr(cell.measure, "read", read)
    rec = harness.run(cell, 4, 1.0)
    assert not rec["correct"]
    bad = "read_p_out" if measure == "knn_class" else "iv_err"
    assert rec["checks"][bad]["value"] > rec["checks"][bad]["limit"]
