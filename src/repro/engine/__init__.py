from repro.engine.semiring import (
    PRESENCE, COUNTING, MIN_MONOID, MAX_MONOID, Semiring,
)
from repro.engine.relation import Relation, from_numpy, to_numpy
from repro.engine.backend import (
    JNP, JnpDispatch, KernelDispatch, PallasDispatch, resolve_backend,
)
from repro.engine.engine import Engine, EngineConfig, EngineStats
from repro.engine.faults import (
    FaultError, FaultPlan, FaultSpec, SimulatedCrash,
)
from repro.engine.observe import REGISTRY, MetricsRegistry, Observation


def make_engine(compiled, config: EngineConfig | None = None,
                incremental: bool = False):
    """Engine factory: ``config.shards >= 2`` selects the sharded
    multi-device driver (engine/shard.py), else the single-device
    Engine. The two are byte-identical in results and iteration counts
    (tests/test_sharded.py). ``incremental=True`` wraps the selected
    driver in an ``IncrementalEngine`` (engine/incremental.py) — the
    two axes compose: ``shards=N`` + ``incremental=True`` maintains the
    materialized state shard-local across the update stream
    (tests/test_update_streams.py)."""
    if incremental:
        from repro.engine.incremental import IncrementalEngine
        return IncrementalEngine(compiled, config)
    if config is not None and int(config.shards or 0) >= 2:
        from repro.engine.shard import ShardedEngine
        return ShardedEngine(compiled, config)
    return Engine(compiled, config)


def __getattr__(name):
    # the resilience layer imports checkpoint/ (and through it jax
    # tree flattening); load it lazily so `import repro.engine` stays
    # checkpoint-free
    if name in ("DurableIncrementalEngine", "ResilienceConfig",
                "SnapshotMismatch", "UpdateLog"):
        from repro.engine import resilience
        return getattr(resilience, name)
    raise AttributeError(name)


__all__ = [
    "PRESENCE", "COUNTING", "MIN_MONOID", "MAX_MONOID", "Semiring",
    "Relation", "from_numpy", "to_numpy",
    "JNP", "JnpDispatch", "KernelDispatch", "PallasDispatch",
    "resolve_backend",
    "Engine", "EngineConfig", "EngineStats", "make_engine",
    "FaultError", "FaultPlan", "FaultSpec", "SimulatedCrash",
    "REGISTRY", "MetricsRegistry", "Observation",
    "DurableIncrementalEngine", "ResilienceConfig", "SnapshotMismatch",
    "UpdateLog",
]
