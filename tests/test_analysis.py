"""Static-analysis subsystem tests (core/analysis): the malformed-IR
corpus (one mutated CompiledProgram per verifier check, each asserting
its named diagnostic fires), the worst-case bound analyzer, and the
runtime arrangement sanitizer — including on-device corruption of
witnesses / PAD tails / shard homing at 2 and 8 shards."""
import jax
import numpy as np
import pytest

from repro.core import ir as I
from repro.core.analysis import (
    SanitizerError, analyze_program, check_relation, check_sharded,
    verify_ir, verify_program,
)
from repro.core.analysis.bounds import analyze_rule
from repro.core.analysis.verify import (
    VerificationError, verify_ir_or_raise,
)
from repro.core.optimizer.pipeline import CompileOptions, compile_program
from repro.engine import Engine, EngineConfig, make_engine
from repro.engine.incremental import IncrementalEngine
from repro.engine.relation import (
    COUNTERS, Relation, UNSORTED, counter_scope, from_numpy,
)
from repro.engine.shard import ShardedRelation

TC = ("tc(x, y) :- edge(x, y).\n"
      "tc(x, z) :- tc(x, y), edge(y, z).\n"
      ".output tc\n.input edge(2)\n")

TRI = ("p(x, z) :- e(x, z).\n"
       "p(x, z) :- p(x, y), p(y, w), e(w, z).\n"
       ".output p\n.input e(2)\n")


def _need(shards: int):
    if shards > len(jax.devices()):
        pytest.skip(f"needs {shards} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")


def _checks(diags):
    return {d.check for d in diags}


def _compiled(src=TC, **kw):
    return compile_program(src, CompileOptions(**kw))


# -- verifier: clean corpus ---------------------------------------------------

def test_corpus_verifies_clean():
    from benchmarks.programs import equivalence_datasets
    for name, (src, _) in equivalence_datasets().items():
        cp = compile_program(src)  # verify=True: raises on violation
        assert verify_program(cp, pass_name="final") == [], name


# -- malformed-IR corpus: one mutation per check ------------------------------
# (constructed below the pipeline on purpose — the pipeline itself
# refuses to emit these, which is what the in-pipeline hooks pin)

def test_dangling_columnref_caught():
    bad = I.Map(I.Scan("e", ("x", "y")), ("x", "nope"))
    diags = verify_ir(bad, where="corpus", pass_name="fusion")
    assert "columnref-resolution" in _checks(diags)
    assert any("nope" in d.message for d in diags)
    assert any("after pass fusion" in str(d) for d in diags)


def test_dangling_join_key_caught():
    j = I.Join(I.Scan("a", ("x", "y")), I.Scan("b", ("y", "z")),
               ("q",), ("x", "y", "z"))
    diags = verify_ir(j)
    assert "columnref-resolution" in _checks(diags)
    assert any("Join key 'q'" in d.message for d in diags)


def test_scan_arity_mismatch_caught():
    cp = _compiled()
    sp = cp.strata[0]
    # widen a scan's schema without touching the declared arity
    bad = I.Map(I.Scan("edge", ("x", "y", "z")), ("x", "y"))
    p = sp.plans[0]
    sp.plans[0] = I.RulePlan(p.head, bad, p.variant, p.source)
    diags = verify_program(cp, pass_name="sharing")
    assert "arity-consistency" in _checks(diags)
    assert any("Scan(edge) has 3 columns" in d.message for d in diags)


def test_concat_arity_mismatch_caught():
    c = I.Concat(I.Scan("a", ("x", "y")), I.Scan("b", ("x",)))
    assert "arity-consistency" in _checks(verify_ir(c))


def test_negation_in_stratum_caught():
    cp = _compiled()
    sp = next(s for s in cp.strata if "tc" in s.idbs)
    p = sp.plans[0]
    # negate the stratum's own IDB under the plan root
    bad = I.Antijoin(p.root, I.Scan("tc", ("x", "y")), ())
    sp.plans[0] = I.RulePlan(p.head, bad, p.variant, p.source)
    diags = verify_program(cp, pass_name="planning")
    assert "negation-in-stratum" in _checks(diags)
    assert any("unstratified negation" in d.message for d in diags)


def test_duplicate_sharedref_def_caught():
    cp = _compiled()
    cp.shared["aaaa"] = I.Distinct(I.Scan("edge", ("x", "y")))
    cp.shared["bbbb"] = I.Distinct(I.Scan("edge", ("x", "y")))
    diags = verify_program(cp, pass_name="sharing")
    assert "sharedref-duplicate-def" in _checks(diags)
    assert any("aaaa" in d.message and "bbbb" in d.message
               for d in diags)


def test_dangling_sharedref_caught():
    diags = verify_ir(I.SharedRef("feed", ("x", "y")), shared={})
    assert "sharedref-dangling" in _checks(diags)


def test_sharedref_cycle_caught():
    cp = _compiled()
    cp.shared["c1"] = I.Distinct(I.SharedRef("c2", ("x", "y")))
    cp.shared["c2"] = I.Distinct(I.SharedRef("c1", ("x", "y")))
    diags = verify_program(cp)
    assert "sharedref-cycle" in _checks(diags)


def test_sharedref_arity_mismatch_caught():
    shared = {"h1": I.Scan("e", ("x", "y"))}
    diags = verify_ir(I.SharedRef("h1", ("a", "b", "c")), shared=shared)
    assert "sharedref-arity" in _checks(diags)


def test_wide_head_caught():
    cp = _compiled()
    cp.arities["tc"] = 9  # above relation.MAX_STORED_COLUMNS
    diags = verify_program(cp, pass_name="sharing")
    assert "stored-arity" in _checks(diags)
    assert any("MAX_STORED_COLUMNS" in d.message for d in diags)


def test_head_arity_mismatch_caught():
    cp = _compiled()
    sp = cp.strata[0]
    p = sp.plans[0]
    sp.plans[0] = I.RulePlan(p.head, I.Map(p.root, p.root.schema[:1]),
                             p.variant, p.source)
    diags = verify_program(cp)
    assert "head-arity" in _checks(diags)


def test_bad_scan_version_caught():
    diags = verify_ir(I.Scan("e", ("x", "y"), version="stale"))
    assert "scan-version" in _checks(diags)


def test_bad_reduce_group_key_caught():
    r = I.Reduce(I.Scan("e", ("x", "y")), ("z",), (("SUM", "y"),),
                 ("z", "y"))
    assert "reduce-group-key" in _checks(verify_ir(r))


def test_verification_error_names_pass():
    bad = I.Map(I.Scan("e", ("x", "y")), ("ghost",))
    with pytest.raises(VerificationError) as exc:
        verify_ir_or_raise(bad, where="r1", pass_name="sip")
    assert "after pass sip" in str(exc.value)
    assert "ghost" in str(exc.value)


@pytest.mark.no_ir_verify
def test_pipeline_names_offending_pass(monkeypatch):
    """A pass that emits malformed IR is named in the diagnostic: break
    fuse() and the pipeline must attribute the damage to 'fusion'."""
    from repro.core.optimizer import pipeline as P

    monkeypatch.setattr(
        P, "fuse", lambda root: I.Map(root, ("__not_a_column__",)))
    with pytest.raises(VerificationError) as exc:
        compile_program(TC, CompileOptions(verify=True))
    assert "after pass fusion" in str(exc.value)


@pytest.mark.no_ir_verify
def test_verify_opt_out_skips_checks(monkeypatch):
    """verify=False + no forced verification: the same broken pass
    slips through compile (caught later only by verify_program)."""
    from repro.core.optimizer import pipeline as P

    monkeypatch.setattr(
        P, "fuse", lambda root: I.Map(root, ("__not_a_column__",)))
    # use_sharing=False: sharing's canonicalization would crash on the
    # malformed Map with a raw KeyError long after the fact — exactly
    # the far-from-cause failure mode the verifier exists to replace
    cp = compile_program(TC, CompileOptions(verify=False,
                                            use_sharing=False))
    assert verify_program(cp) != []


# -- worst-case bounds --------------------------------------------------------

def test_bound_triangle_agm():
    """Cyclic triangle query: AGM gives N^1.5, far below the N^2
    pairwise-join bound."""
    n = 1024
    j1 = I.Join(I.Scan("r", ("a", "b")), I.Scan("s", ("b", "c")),
                ("b",), ("a", "b", "c"))
    tri = I.Join(j1, I.Scan("t", ("c", "a")), ("c", "a"),
                 ("a", "b", "c"))
    rep = analyze_rule(I.RulePlan("q", tri, -1, "triangle"),
                       {"r": n, "s": n, "t": n})
    assert rep.log2_out == pytest.approx(1.5 * np.log2(n), abs=0.01)


def test_bound_fd_key_covers_side():
    """Join keys covering one whole side of a base relation: each left
    row matches at most one right row, so |big| bounds the join even
    though |keys| is huge."""
    j = I.Join(I.Scan("big", ("x", "y")), I.Scan("keys", ("y",)),
               ("y",), ("x", "y"))
    rep = analyze_rule(I.RulePlan("q", j, -1, "fd"),
                       {"big": 4096, "keys": 1 << 20})
    assert rep.log2_out == pytest.approx(12.0, abs=0.01)


def test_bound_concat_sums():
    c = I.Concat(I.Scan("a", ("x",)), I.Scan("b", ("x",)))
    rep = analyze_rule(I.RulePlan("q", c, -1, ""), {"a": 8, "b": 8})
    assert rep.log2_out == pytest.approx(4.0, abs=0.01)


def test_bound_cartesian_peak_recorded():
    """A keyless cross product shows up as the peak intermediate."""
    cross = I.Join(I.Scan("a", ("x",)), I.Scan("b", ("y",)),
                   (), ("x", "y"))
    rep = analyze_rule(I.RulePlan("q", cross, -1, "cross"),
                       {"a": 4096, "b": 4096})
    assert rep.log2_peak == pytest.approx(24.0, abs=0.01)
    assert rep.peak_node == "Join"


def test_bound_flags_bad_join_order():
    """The analyzer separates the optimized triangle plan from the
    blow-up-prone listing order (the robustness-bench claim,
    statically)."""
    sizes = {"e": 90, "p": 4096}
    good = analyze_program(compile_program(TRI, CompileOptions()), sizes)
    bad = analyze_program(
        compile_program(TRI, CompileOptions(use_planner=False,
                                            use_sip=False)), sizes)
    assert good.log2_peak <= bad.log2_peak + 1e-9
    assert max(r.risk for r in good.rules) <= \
        max(r.risk for r in bad.rules)


def test_analyze_program_corpus_runs():
    from benchmarks.programs import equivalence_datasets
    for name, (src, edbs) in equivalence_datasets().items():
        rep = analyze_program(compile_program(src),
                              {k: len(v) for k, v in edbs.items()})
        assert rep.rules, name
        assert np.isfinite(rep.log2_peak), name


# -- runtime sanitizer: relation-level corruption -----------------------------

def _rel(rows, cap=16, **kw):
    return from_numpy(np.array(rows), cap, **kw)


def test_sanitizer_clean_relation():
    assert check_relation(_rel([[1, 2], [3, 4]]), "t") == []


def test_sanitizer_catches_lying_witness():
    r = _rel([[0, 9], [1, 1], [2, 5]])
    # rows are NOT sorted by column 1 — the witness is a lie
    lying = Relation(r.data, r.val, r.n, order=(1, 0))
    out = check_relation(lying, "t")
    assert any("mis-sorted" in v and "order=(1, 0)" in v for v in out)


def test_sanitizer_catches_pad_tail_corruption():
    r = _rel([[1, 2], [3, 4]], cap=8)
    data = np.asarray(r.data).copy()
    data[5] = [7, 7]  # ghost row past n
    out = check_relation(Relation(data, r.val, r.n), "t")
    assert any("PAD-tail" in v for v in out)


def test_sanitizer_catches_duplicates():
    data = np.full((8, 2), np.iinfo(np.int32).max, np.int32)
    data[:3] = [[1, 1], [1, 1], [2, 2]]
    out = check_relation(Relation(data, None, np.int32(3)), "t")
    assert any("duplicate" in v for v in out)


def test_sanitizer_catches_unsorted_duplicates():
    data = np.full((8, 2), np.iinfo(np.int32).max, np.int32)
    data[:3] = [[5, 5], [1, 1], [5, 5]]
    rel = Relation(data, None, np.int32(3), order=UNSORTED)
    out = check_relation(rel, "t")
    assert any("duplicate" in v for v in out)


def test_sanitizer_catches_bad_n():
    r = _rel([[1, 2]], cap=8)
    out = check_relation(Relation(r.data, r.val, np.int32(99)), "t")
    assert any("outside" in v for v in out)


def test_sanitizer_catches_value_tail():
    r = _rel([[1], [2]], cap=8, val=np.array([5, 6]), val_identity=0)
    val = np.asarray(r.val).copy()
    val[6] = 123  # identity slot clobbered
    out = check_relation(Relation(r.data, val, r.n), "t",
                         val_identity=0)
    assert any("value tail" in v for v in out)


# -- runtime sanitizer: sharded corruption (2 and 8 shards) -------------------

def _sharded_fixture(shards):
    """A correctly-homed ShardedRelation built by the engine's own
    scatter path."""
    eng = make_engine(compile_program(TC), EngineConfig(shards=shards))
    rows = np.array([[i, i + 1] for i in range(24)])
    srel = eng._stored({"edge": from_numpy(rows, 64)})["edge"]
    assert isinstance(srel, ShardedRelation)
    return srel


def _rolled(srel):
    """Every block shifted one shard over: blocks stay valid
    arrangements internally, but every live row is now stored on the
    wrong shard — ONLY the homing invariant breaks."""
    return ShardedRelation(
        np.roll(np.asarray(srel.data), 1, axis=0),
        np.roll(np.asarray(srel.val), 1, axis=0)
        if srel.val is not None else None,
        np.roll(np.asarray(srel.n), 1))


@pytest.mark.parametrize("shards", (2, 8))
def test_sanitizer_sharded_clean(shards):
    _need(shards)
    assert check_sharded(_sharded_fixture(shards), "edge") == []


@pytest.mark.parametrize("shards", (2, 8))
def test_sanitizer_catches_stray_shard_rows(shards):
    _need(shards)
    out = check_sharded(_rolled(_sharded_fixture(shards)), "edge")
    assert any("homed to shard" in v for v in out)
    assert not any("mis-sorted" in v for v in out)  # homing only


@pytest.mark.parametrize("shards", (2, 8))
def test_sanitizer_catches_block_corruption(shards):
    """A corrupted witness inside one block is caught block-locally."""
    _need(shards)
    srel = _sharded_fixture(shards)
    data = np.asarray(srel.data).copy()
    n = np.asarray(srel.n)
    s = int(np.argmax(n >= 2))
    if n[s] < 2:
        pytest.skip("no block with 2+ rows at this shard count")
    data[s, [0, 1]] = data[s, [1, 0]]  # break block sortedness
    out = check_sharded(ShardedRelation(data, srel.val, srel.n), "e")
    assert any(f"[shard {s}/" in v and "mis-sorted" in v for v in out)


# -- sanitizer wiring: engine layers named, clean end-to-end ------------------

def test_engine_layer_named_in_error():
    eng = Engine(_compiled(), EngineConfig(check_invariants=True))
    r = _rel([[0, 9], [1, 1], [2, 5]])
    lying = Relation(r.data, r.val, r.n, order=(1, 0))
    with pytest.raises(SanitizerError) as exc:
        eng._sanitize_env({("tc", I.FULL): lying},
                          "stratum s0 boundary")
    msg = str(exc.value)
    assert "layer 'engine'" in msg and "stratum s0 boundary" in msg
    assert "tc" in msg


def test_engine_sanitize_off_by_default():
    eng = Engine(_compiled(), EngineConfig())
    r = _rel([[0, 9], [1, 1], [2, 5]])
    lying = Relation(r.data, r.val, r.n, order=(1, 0))
    eng._sanitize_env({("tc", I.FULL): lying}, "x")  # no raise


def test_shard_layer_named_in_error():
    _need(2)
    eng = make_engine(_compiled(),
                      EngineConfig(check_invariants=True, shards=2))
    bad = _rolled(_sharded_fixture(2))
    with pytest.raises(SanitizerError) as exc:
        eng._sanitize_env({("edge", I.FULL): bad}, "stratum s0 boundary")
    assert "layer 'shard'" in str(exc.value)


@pytest.mark.parametrize("backend", (
    "jnp", pytest.param("pallas-interpret", id="pallas")))
def test_run_sanitizer_clean_backends(backend):
    """check_invariants=True full runs stay clean on both kernel
    backends."""
    rng = np.random.default_rng(5)
    edges = rng.integers(0, 30, size=(60, 2))
    eng = Engine(_compiled(), EngineConfig(
        check_invariants=True, kernel_backend=backend,
        idb_cap=1 << 11, intermediate_cap=1 << 13))
    out, _ = eng.run({"edge": edges})
    assert out["tc"].shape[0] > 0


@pytest.mark.parametrize("shards", (2, 8))
def test_run_sanitizer_clean_sharded(shards):
    _need(shards)
    rng = np.random.default_rng(5)
    edges = rng.integers(0, 30, size=(60, 2))
    eng = make_engine(_compiled(), EngineConfig(
        check_invariants=True, shards=shards,
        idb_cap=1 << 11, intermediate_cap=1 << 13))
    out, _ = eng.run({"edge": edges})
    ref, _ = Engine(_compiled(), EngineConfig(
        idb_cap=1 << 11, intermediate_cap=1 << 13)).run({"edge": edges})
    np.testing.assert_array_equal(out["tc"], ref["tc"])


def test_incremental_apply_sanitized():
    rng = np.random.default_rng(7)
    edges = rng.integers(0, 25, size=(40, 2))
    inc = IncrementalEngine(_compiled(), EngineConfig(
        check_invariants=True, idb_cap=1 << 11,
        intermediate_cap=1 << 13))
    inc.initialize({"edge": edges})
    snap = inc.apply(inserts={"edge": np.array([[40, 41], [41, 42]])})
    assert (40, 41) in set(map(tuple, snap["tc"]))
    snap = inc.apply(deletes={"edge": edges[:5]})
    assert "tc" in snap


def test_sanitizer_sampling_every_nth(monkeypatch):
    """check_invariants=N runs the sanitizer at every Nth stratum
    boundary only (True = every boundary, False = never); the counter
    persists across calls so a serving loop amortizes the O(rows)
    host transfers. N=1 degenerates to True (guards the
    isinstance(True, int) trap: True must mean 1, not 'sample')."""
    import repro.core.analysis.sanitize as S
    calls = []
    monkeypatch.setattr(
        S, "sanitize_env", lambda *a, **k: calls.append(1))
    env = {("tc", I.FULL): _rel([[1, 2]])}

    def boundaries(ci, n=9):
        del calls[:]
        eng = Engine(_compiled(), EngineConfig(check_invariants=ci))
        for _ in range(n):
            eng._sanitize_env(env, "boundary")
        return len(calls)

    assert boundaries(False) == 0
    assert boundaries(True) == 9
    assert boundaries(1) == 9
    assert boundaries(3) == 3
    assert boundaries(4) == 2


# -- counter scoping (satellite) ----------------------------------------------

def test_counter_scope_isolates_and_accumulates():
    base = dict(COUNTERS)
    with counter_scope() as outer:
        COUNTERS["sorts"] += 2
        with counter_scope() as inner:
            COUNTERS["sorts"] += 3
        assert inner["sorts"] == 3
        # outer scope sees its own work plus the nested window's
        assert COUNTERS["sorts"] == 5
    assert outer["sorts"] == 5
    # globals fully restored + accumulated
    assert COUNTERS["sorts"] == base["sorts"] + 5


def test_counter_scope_restores_on_error():
    base = dict(COUNTERS)
    with pytest.raises(RuntimeError):
        with counter_scope() as c:
            COUNTERS["sorts"] += 1
            raise RuntimeError("boom")
    assert c["sorts"] == 1
    assert COUNTERS["sorts"] == base["sorts"] + 1
