"""Seeded fault injection (runtime/faults.py): spec grammar round-trips,
clause semantics (kill / crash / slow / flaky / spike), determinism of the
seeded draws under any interleaving, and the injector's dispatch
accounting."""
import pytest

from repro.runtime.faults import (FaultClause, FaultInjector, FaultPlan,
                                  InjectedFault, ReplicaDead, parse_clause)


# ---------------------------------------------------------------------------
# grammar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("text,kind,replica", [
    ("kill:r1@5", "kill", 1),
    ("crash:r0@3", "crash", 0),
    ("slow:r2@4:0.25", "slow", 2),
    ("flaky:r1:0.3", "flaky", 1),
    ("spike:r0:0.5:0.01", "spike", 0),
])
def test_parse_clause_round_trips(text, kind, replica):
    c = parse_clause(text)
    assert c.kind == kind and c.replica == replica
    assert parse_clause(str(c)) == c


@pytest.mark.parametrize("bad", [
    "", "kill:r1", "kill:1@5", "slow:r0@1", "flaky:r0", "explode:r0@1",
    "kill:r1@5 trailing",
])
def test_parse_clause_rejects_garbage(bad):
    with pytest.raises(ValueError):
        parse_clause(bad)


def test_from_spec_multi_clause_and_str_round_trip():
    plan = FaultPlan.from_spec(" kill:r1@5, slow:r0@0:0.2 ", seed=7)
    assert [c.kind for c in plan.clauses] == ["kill", "slow"]
    assert str(plan) == "kill:r1@5,slow:r0@0:0.2"
    with pytest.raises(ValueError):
        FaultPlan.from_spec("  ,  ")


# ---------------------------------------------------------------------------
# clause semantics (faults_for is a pure function of (replica, n))
# ---------------------------------------------------------------------------

def test_kill_is_permanent_from_threshold():
    plan = FaultPlan.from_spec("kill:r1@2")
    for n in (0, 1):
        assert plan.faults_for(1, n) == (0.0, None)
    for n in (2, 3, 100):
        _, exc = plan.faults_for(1, n)
        assert isinstance(exc, ReplicaDead)
    # other replicas are untouched
    assert plan.faults_for(0, 50) == (0.0, None)


def test_crash_fires_exactly_once():
    plan = FaultPlan.from_spec("crash:r0@3")
    hits = [n for n in range(10)
            if plan.faults_for(0, n)[1] is not None]
    assert hits == [3]
    _, exc = plan.faults_for(0, 3)
    assert isinstance(exc, InjectedFault) and not isinstance(exc, ReplicaDead)


def test_slow_adds_delay_from_threshold():
    plan = FaultPlan.from_spec("slow:r0@2:0.5")
    assert plan.faults_for(0, 1) == (0.0, None)
    delay, exc = plan.faults_for(0, 2)
    assert delay == pytest.approx(0.5) and exc is None
    # clauses stack: two slow clauses on the same replica sum
    plan2 = FaultPlan.from_spec("slow:r0@0:0.5,slow:r0@0:0.25")
    assert plan2.faults_for(0, 0)[0] == pytest.approx(0.75)


def test_flaky_and_spike_are_seeded_and_deterministic():
    a = FaultPlan.from_spec("flaky:r0:0.3,spike:r0:0.4:0.01", seed=11)
    b = FaultPlan.from_spec("flaky:r0:0.3,spike:r0:0.4:0.01", seed=11)

    def fingerprint(plan, n):
        delay, exc = plan.faults_for(0, n)
        return (delay, None if exc is None else (type(exc), str(exc)))

    assert [fingerprint(a, n) for n in range(200)] \
        == [fingerprint(b, n) for n in range(200)]
    # probabilities are honored at the extremes
    never = FaultPlan.from_spec("flaky:r0:0", seed=1)
    always = FaultPlan.from_spec("flaky:r0:1", seed=1)
    assert all(never.faults_for(0, n)[1] is None for n in range(50))
    assert all(always.faults_for(0, n)[1] is not None for n in range(50))
    # a different seed flips some per-dispatch outcomes
    c = FaultPlan.from_spec("flaky:r0:0.3", seed=12)
    flips = sum((a.faults_for(0, n)[1] is None)
                != (c.faults_for(0, n)[1] is None) for n in range(200))
    assert flips > 0


def test_flaky_rate_is_roughly_p():
    plan = FaultPlan.from_spec("flaky:r0:0.3", seed=5)
    hits = sum(plan.faults_for(0, n)[1] is not None for n in range(1000))
    assert 200 < hits < 400


# ---------------------------------------------------------------------------
# injector
# ---------------------------------------------------------------------------

def test_injector_counts_and_raises_per_replica():
    inj = FaultInjector(FaultPlan.from_spec("kill:r1@1"))
    ok = inj.wrap(0, lambda p: p * 2)
    dead = inj.wrap(1, lambda p: p * 3)
    assert ok(21) == 42
    assert dead(1) == 3            # r1's dispatch 0 is pre-threshold
    with pytest.raises(ReplicaDead):
        dead(1)
    with pytest.raises(ReplicaDead):
        dead(1)
    assert inj.dispatches[0] == 1
    assert inj.dispatches[1] == 3  # failed dispatches still count
    assert inj.injected["exceptions"] == 2


def test_injector_underlying_fn_not_called_on_injection():
    calls = []
    inj = FaultInjector(FaultPlan.from_spec("crash:r0@0"))
    fn = inj.wrap(0, lambda p: calls.append(p) or p)
    with pytest.raises(InjectedFault):
        fn("x")
    assert calls == []             # the fault pre-empts the engine
    assert fn("y") == "y"          # crash recovers after its one dispatch
    assert calls == ["y"]
