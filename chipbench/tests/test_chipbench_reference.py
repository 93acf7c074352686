"""The references pass the engine's answers, catch a corrupted answer of
each problem, and fail the control."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from repro.ampc import AmpcEngine  # noqa: E402

from chipbench import inputs, reference  # noqa: E402

PROBLEMS = ["mis", "matching", "connectivity", "msf"]


def _graph(scale: int, seed: int):
    rng = np.random.default_rng(seed)
    g = inputs.relabel(inputs.UGraph(
        1 << scale, inputs.kronecker_edges(scale, 16, 0)), rng)
    return inputs.UGraph(g.n, g.edges, inputs.distinct_weights(g.m, rng))


@pytest.fixture(scope="module")
def answers():
    g = _graph(7, 5)
    eng = AmpcEngine(seed=0)
    return g, {p: eng.solve(g, p, seed=11).output for p in PROBLEMS}


@pytest.mark.parametrize("problem", PROBLEMS)
def test_engine_answers_pass(problem, answers):
    g, out = answers
    found = reference.check(problem, g, out[problem])
    assert set(found) == set(reference.CHECKS[problem])
    assert not any(found.values()), found


def _corrupt(problem, g, out):
    out = np.array(out)
    if problem == "mis":             # add a neighbour of a member
        u, v = g.edges[out[g.edges[:, 0]] & ~out[g.edges[:, 1]]][0]
        out[v] = True
    elif problem == "matching":      # drop one matched edge
        out[np.flatnonzero(out)[0]] = False
    elif problem == "connectivity":  # split one vertex off its component
        out[g.edges[0, 1]] = g.n + 7
    else:                            # swap a forest edge for another edge
        out[np.flatnonzero(out)[0]] = False
        out[np.flatnonzero(~out)[0]] = True
    return out


@pytest.mark.parametrize("problem", PROBLEMS)
def test_references_catch_a_corrupted_answer(problem, answers):
    g, out = answers
    found = reference.check(problem, g, _corrupt(problem, g, out[problem]))
    assert sum(found.values()) > 0


@pytest.mark.parametrize("problem", PROBLEMS)
def test_wrong_shape_or_missing_answer_fails_every_check(problem, answers):
    g, out = answers
    for bad in (None, np.asarray(out[problem])[:-1]):
        assert all(v > 0 for v in reference.check(problem, g, bad).values())


@pytest.mark.parametrize("problem", PROBLEMS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_comparison(problem, seed):
    """The reference with one guarantee broken (fixpoints capped at two
    waves, msf weights in bfloat16) reads above the limit 0, at scale
    12."""
    g = _graph(12, seed)
    found = reference.check(problem, g,
                            reference.control_answer(problem, g, seed))
    assert sum(found.values()) > 0, found


def test_uncapped_control_algorithms_are_correct():
    """Run to their end, the control's own fixpoints pass: it is the cap
    that fails them, not a fault of the control."""
    g = _graph(9, 4)
    for problem, answer in (
            ("mis", reference._capped_greedy_mis(g, 3, g.n)),
            ("matching", reference._capped_greedy_matching(g, 3, g.m)),
            ("connectivity", reference._capped_label_propagation(g, g.n))):
        assert not any(reference.check(problem, g, answer).values())
