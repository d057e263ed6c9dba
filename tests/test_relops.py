"""Unit + property tests for the physical relational operators."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.engine import relops as R
from repro.engine.relation import PAD, Relation, from_numpy, to_numpy
from repro.engine.semiring import COUNTING, MIN_MONOID, PRESENCE


def rel_of(rows, cap=64, **kw):
    return from_numpy(np.asarray(rows), cap, **kw)


def test_from_numpy_sorted_distinct():
    r = rel_of([[3, 1], [1, 2], [3, 1], [0, 9]])
    assert to_numpy(r).tolist() == [[0, 9], [1, 2], [3, 1]]


def test_dedupe_presence():
    data = jnp.array([[2, 1], [1, 1], [2, 1], [PAD, PAD]], jnp.int32)
    out, ovf = R.dedupe(data, None, PRESENCE, 8)
    assert not bool(ovf)
    assert to_numpy(out).tolist() == [[1, 1], [2, 1]]


def test_dedupe_counting_combines_and_drops_zero():
    data = jnp.array([[1, 1], [1, 1], [2, 2], [2, 2]], jnp.int32)
    val = jnp.array([2, 3, 1, -1], jnp.int32)
    out, _ = R.dedupe(data, val, COUNTING, 8)
    rows = to_numpy(out).tolist()
    assert rows == [[1, 1]]          # (2,2) count cancels to 0
    assert int(out.val[0]) == 5


def test_dedupe_min_monoid():
    data = jnp.array([[7], [7], [3]], jnp.int32)
    val = jnp.array([5, 2, 9], jnp.int32)
    out, _ = R.dedupe(data, val, MIN_MONOID, 8)
    assert to_numpy(out).tolist() == [[3], [7]]
    assert out.val[:2].tolist() == [9, 2]


def test_join_inner():
    left = rel_of([[0, 10], [1, 11], [2, 12]])
    right = rel_of([[10, 5], [10, 6], [12, 7]])
    data, val, valid, total, ovf = R.join(
        left, right, (1,), (0,), (0, 1), (1,), PRESENCE, 32)
    assert not bool(ovf)
    got = {tuple(r) for r, v in zip(np.asarray(data), np.asarray(valid)) if v}
    assert got == {(0, 10, 5), (0, 10, 6), (2, 12, 7)}
    assert int(total) == 3


def test_join_overflow_flag():
    left = rel_of([[0, 1]] * 1 + [[i, 1] for i in range(8)], cap=16)
    right = rel_of([[1, i] for i in range(8)], cap=16)
    *_, total, ovf = R.join(left, right, (1,), (0,), (0,), (1,),
                            PRESENCE, 4)
    assert bool(ovf) and int(total) > 4


def test_cross_join_empty_keys():
    left = rel_of([[1], [2]])
    right = rel_of([[7], [8], [9]])
    data, val, valid, total, _ = R.join(
        left, right, (), (), (0,), (0,), PRESENCE, 16)
    assert int(total) == 6


def test_semijoin_antijoin():
    left = rel_of([[0, 1], [1, 2], [2, 3]])
    right = rel_of([[1], [3]])
    semi, _ = R.semijoin(left, right, (1,), (0,))
    assert to_numpy(semi).tolist() == [[0, 1], [2, 3]]
    anti, _ = R.antijoin(left, right, (1,), (0,))
    assert to_numpy(anti).tolist() == [[1, 2]]


def test_semijoin_zero_key_ground_guard():
    """Zero-key semijoin (ground guard: 'is right non-empty?') keeps
    exactly the live left rows — regression: the PAD tail must not be
    resurrected as live rows (it made guarded fixpoints never drain)."""
    left = rel_of([[0, 1], [1, 2]])
    occupied = rel_of([[9]])
    semi, _ = R.semijoin(left, occupied, (), ())
    assert int(semi.n) == 2
    assert to_numpy(semi).tolist() == [[0, 1], [1, 2]]
    emptied = Relation(occupied.data, occupied.val,
                       jnp.zeros((), jnp.int32))
    semi, _ = R.semijoin(left, emptied, (), ())
    assert int(semi.n) == 0
    anti, _ = R.antijoin(left, emptied, (), ())
    assert int(anti.n) == 2


def test_difference():
    a = rel_of([[1, 1], [2, 2], [3, 3]])
    b = rel_of([[2, 2]])
    d, _ = R.difference(a, b)
    assert to_numpy(d).tolist() == [[1, 1], [3, 3]]


def test_merge_with_delta_presence():
    full = rel_of([[1], [2]])
    derived = rel_of([[2], [3]])
    nf, delta, ovf = R.merge_with_delta(full, derived, PRESENCE, 64)
    assert to_numpy(nf).tolist() == [[1], [2], [3]]
    assert to_numpy(delta).tolist() == [[3]]


def test_merge_with_delta_min():
    full = from_numpy(np.array([[1], [2]]), 64, val=np.array([5, 5]),
                      val_identity=MIN_MONOID.identity)
    derived = from_numpy(np.array([[2], [3]]), 64, val=np.array([3, 9]),
                         val_identity=MIN_MONOID.identity)
    nf, delta, _ = R.merge_with_delta(full, derived, MIN_MONOID, 64)
    assert to_numpy(nf).tolist() == [[1], [2], [3]]
    assert nf.val[:3].tolist() == [5, 3, 9]
    # delta: improved rows only (2 improved to 3; 3 is new)
    assert to_numpy(delta).tolist() == [[2], [3]]


def test_reduce_groups_count_sum_min_max():
    r = rel_of([[0, 5], [0, 7], [1, 2], [1, 9], [1, 4]])
    out, ovf = R.reduce_groups(r, (0,), (("COUNT", 1), ("SUM", 1),
                                         ("MIN", 1), ("MAX", 1)), 16)
    rows = {tuple(x) for x in to_numpy(out)}
    assert rows == {(0, 2, 12, 5, 7), (1, 3, 15, 2, 9)}


def test_scatter_compact_empty_keep():
    """Regression: all-False keep must yield n == 0 and an all-PAD
    buffer (the old first `n` assignment read pos[-1] == -1 here)."""
    data = jnp.array([[1, 2], [3, 4], [5, 6]], jnp.int32)
    keep = jnp.zeros((3,), bool)
    d, v, n, ovf = R._scatter_compact(data, None, keep, 4, 0)
    assert int(n) == 0
    assert not bool(ovf)
    assert bool((d == PAD).all())
    assert v is None


def test_scatter_compact_empty_keep_with_val():
    data = jnp.array([[9]], jnp.int32)
    val = jnp.array([7], jnp.int32)
    d, v, n, _ = R._scatter_compact(data, val, jnp.zeros((1,), bool),
                                    2, 0)
    assert int(n) == 0
    assert v.tolist() == [0, 0]


def test_arrange_orders_by_key():
    r = rel_of([[0, 9], [1, 1], [2, 5]])
    a = R.arrange(r, (1,))
    col1 = to_numpy(a)[:, 1].tolist()
    assert col1 == sorted(col1)


def test_membership_ground_guard():
    left = rel_of([[1], [2]])
    nonempty = rel_of([[9]])
    m = R.membership(left, nonempty, (), ())
    assert bool(m[0]) and bool(m[1])
    hollow = Relation(
        jnp.full((4, 1), PAD, jnp.int32), None, jnp.zeros((), jnp.int32))
    m2 = R.membership(left, hollow, (), ())
    assert not bool(m2[:2].any())


# -- property-style randomized sweeps (lightweight hypothesis) --------------

@pytest.mark.parametrize("seed", range(5))
def test_join_matches_numpy_oracle(seed):
    rng = np.random.default_rng(seed)
    ln = rng.integers(1, 40)
    rn = rng.integers(1, 40)
    left = rng.integers(0, 8, size=(ln, 2))
    right = rng.integers(0, 8, size=(rn, 2))
    lrel, rrel = rel_of(left, 64), rel_of(right, 64)
    data, val, valid, total, ovf = R.join(
        lrel, rrel, (1,), (0,), (0, 1), (1,), PRESENCE, 4096)
    got = {tuple(r) for r, v in zip(np.asarray(data), np.asarray(valid))
           if v}
    lset, rset = set(map(tuple, left)), set(map(tuple, right))
    expect = {(a, b, c) for (a, b) in lset for (b2, c) in rset if b == b2}
    assert got == expect


@pytest.mark.parametrize("seed", range(5))
def test_set_ops_match_python(seed):
    rng = np.random.default_rng(100 + seed)
    a = rng.integers(0, 10, size=(rng.integers(1, 30), 2))
    b = rng.integers(0, 10, size=(rng.integers(1, 30), 2))
    ra, rb = rel_of(a, 64), rel_of(b, 64)
    sa, sb = set(map(tuple, a)), set(map(tuple, b))
    merged, _ = R.merge(ra, rb, PRESENCE, 128)
    assert set(map(tuple, to_numpy(merged))) == sa | sb
    diff, _ = R.difference(ra, rb)
    assert set(map(tuple, to_numpy(diff))) == sa - sb
    semi, _ = R.semijoin(ra, rb, (0, 1), (0, 1))
    assert set(map(tuple, to_numpy(semi))) == sa & sb


# -- device op names ----------------------------------------------------------

SCOPED = {
    "join": lambda a, b: R.join(a, b, (1,), (0,), (0, 1), (1,), PRESENCE, 32),
    "membership": lambda a, b: R.membership(a, b, (1,), (0,)),
    "arrange": lambda a, b: R.arrange(a, (1,)),
    "dedupe": lambda a, b: R.dedupe(a.data, None, PRESENCE, 64),
    "merge": lambda a, b: R.merge(a, b, PRESENCE, 128),
    "merge_with_delta": lambda a, b: R.merge_with_delta(a, b, PRESENCE, 128),
    "concat_all": lambda a, b: R.concat_all([a, b], PRESENCE, 128),
    "reduce_groups": lambda a, b: R.reduce_groups(a, (0,), (("MIN", 1),), 64),
}


@pytest.mark.parametrize("relop", sorted(SCOPED))
def test_a_relops_ops_carry_its_name(relop):
    """Each public relop runs under a ``jax.named_scope`` of its name,
    so its ops' ``op_name`` (a profiler's ``tf_op``) names it."""
    a = rel_of([[0, 10], [1, 11], [2, 12]])
    b = rel_of([[10, 5], [12, 7]])
    text = jax.jit(SCOPED[relop]).lower(a, b).as_text(debug_info=True)
    assert set(re.findall(r'"jit\(<lambda>\)/(\w+)/', text)) == {relop}
