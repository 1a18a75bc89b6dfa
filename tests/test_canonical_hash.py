"""Canonical content hashing: dict-order- and float-repr-invariance, JSON
round-trip stability, problem/scenario fingerprints — the service's cache
key, but useful standalone."""

import json

import numpy as np
import pytest

from repro.core import Scenario, build_problem, mri_system, mri_workload
from repro.core.workload_model import canonical_hash, problem_fingerprint


# ---------------------------------------------------------------------------
# invariances
# ---------------------------------------------------------------------------

def test_dict_key_order_is_irrelevant():
    a = {"alpha": 1.0, "beta": 2.0, "mode": "fixed"}
    b = {}
    for k in reversed(list(a)):
        b[k] = a[k]
    assert list(a) != list(b)  # genuinely different insertion order
    assert canonical_hash(a) == canonical_hash(b)


def test_nested_key_reordering_hashes_identically():
    a = {"w": {"x": [1, {"p": 1, "q": 2}], "y": 3}, "v": 4}
    b = {"v": 4, "w": {"y": 3, "x": [1, {"q": 2, "p": 1}]}}
    assert canonical_hash(a) == canonical_hash(b)


def test_json_roundtrip_hashes_identically():
    obj = {
        "name": "s",
        "weights": {"alpha": 1.0, "beta": 0.5},
        "sizes": (5, 50, 500),  # tuple → list through JSON
        "flags": [True, False, None],
        "threshold": 25,
    }
    rt = json.loads(json.dumps(obj))
    assert isinstance(rt["sizes"], list)
    assert canonical_hash(obj) == canonical_hash(rt)


def test_number_spelling_is_irrelevant():
    assert canonical_hash({"x": 1}) == canonical_hash({"x": 1.0})
    assert canonical_hash(json.loads('{"x": 1.00}')) == canonical_hash({"x": 1})
    assert canonical_hash(0.0) == canonical_hash(-0.0)
    assert canonical_hash(float("nan")) == canonical_hash(float("nan"))
    assert canonical_hash(float("inf")) != canonical_hash(float("-inf"))


def test_large_int_spelling_invariance_tracks_float64_exactness():
    # exactly float64-representable beyond 2**53: int and float spellings
    # of the SAME value must agree
    big = 2**53 + 2
    assert float(big) == big
    assert canonical_hash(big) == canonical_hash(float(big))
    assert canonical_hash(2**60) == canonical_hash(2.0**60)
    # not float64-representable: distinct from its nearest float (they are
    # genuinely different values)
    odd = 2**53 + 1
    assert float(odd) != odd or int(float(odd)) != odd
    assert canonical_hash(odd) != canonical_hash(float(odd))
    assert canonical_hash(odd) != canonical_hash(odd + 2)
    # huge ints (float overflow) still hash stably
    assert canonical_hash(10**400) == canonical_hash(10**400)
    assert canonical_hash(10**400) != canonical_hash(-(10**400))


def test_different_content_different_hash():
    base = {"a": 1.0, "b": [1, 2, 3]}
    assert canonical_hash(base) != canonical_hash({"a": 1.0, "b": [1, 2, 4]})
    assert canonical_hash(base) != canonical_hash({"a": 1.5, "b": [1, 2, 3]})
    assert canonical_hash(base) != canonical_hash({"a": 1.0, "c": [1, 2, 3]})
    assert canonical_hash([1, 2]) != canonical_hash([2, 1])  # lists are ordered
    assert canonical_hash("1") != canonical_hash(1)  # strings are not numbers


def test_numpy_arrays_normalize_dtype_not_kind():
    f32 = np.array([1.0, 2.5], dtype=np.float32)
    f64 = np.array([1.0, 2.5], dtype=np.float64)
    assert canonical_hash(f32) == canonical_hash(f64)
    assert canonical_hash(np.array([[1.0, 2.0]])) != canonical_hash(
        np.array([1.0, 2.0])
    )  # shape matters
    assert canonical_hash(np.array([1.0, np.inf])) == canonical_hash(
        np.array([1.0, np.inf])
    )


def test_unhashable_type_raises():
    with pytest.raises(TypeError, match="canonical_hash"):
        canonical_hash(object())


# ---------------------------------------------------------------------------
# problem / scenario fingerprints
# ---------------------------------------------------------------------------

def test_problem_fingerprint_stable_across_rebuilds():
    a = build_problem(mri_system(), mri_workload())
    b = build_problem(mri_system(), mri_workload())
    assert problem_fingerprint(a) == problem_fingerprint(b)


def test_problem_fingerprint_sees_semantic_changes():
    a = build_problem(mri_system(), mri_workload())
    b = build_problem(mri_system(), mri_workload())
    b.durations[0, 0] *= 2.0  # a monitor-refreshed speed would do this
    assert problem_fingerprint(a) != problem_fingerprint(b)
    c = build_problem(mri_system(), mri_workload())
    c.feasible[:, 1] = False  # a node failure would do this
    assert problem_fingerprint(a) != problem_fingerprint(c)


def test_problem_fingerprint_is_kept_and_freezes_what_it_covers(monkeypatch):
    """The fingerprint is computed once per problem: a second call hashes
    nothing, and an in-place change afterwards raises instead of leaving
    the kept key stale."""
    from repro.core import workload_model

    a = build_problem(mri_system(), mri_workload())
    key = problem_fingerprint(a)
    monkeypatch.setattr(workload_model, "canonical_hash", None)  # any hash would fail
    assert problem_fingerprint(a) == key
    for array in (a.durations, a.feasible, a.dtr, a.pred_matrix, a.release):
        assert not array.flags.writeable
    with pytest.raises(ValueError, match="read-only"):
        a.feasible[:, 1] = False


def test_scenario_fingerprint_survives_json_roundtrip():
    s = Scenario(name="fp", system=mri_system(), workload=mri_workload())
    from repro.core.api import scenario_from_json

    rt = scenario_from_json(json.loads(json.dumps(s.to_json())))
    assert rt.fingerprint() == s.fingerprint()
    assert s.replace(name="other").fingerprint() != s.fingerprint()
