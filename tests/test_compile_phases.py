"""Set-up by phase (ISSUE 37): ``obs.compiles.listen_to_compile_phases``
adds JAX's own compile events to
``dl4j_compile_phase_seconds_total{phase=}``; registered once a process by
``utils.compile_cache.enable_compile_cache``."""
import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from deeplearning4j_tpu.kernels import autotune
from deeplearning4j_tpu.obs import compiles, get_registry
from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache

PHASES = ("trace", "lower", "backend")


@pytest.fixture()
def listening(monkeypatch, tmp_path):
    """The listener on, and the cache directory a temporary one where the
    helper would otherwise make ``<checkout>/.jax_cache``."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    enable_compile_cache()


def _seconds():
    seconds = compiles.phase_counters()[0]
    return {p: seconds.value(phase=p) for p in PHASES}


def test_a_fresh_jit_moves_every_phase_and_a_second_call_none(listening):
    before = _seconds()
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum() + 37.0)
    x = jnp.ones((24, 8))
    f(x).block_until_ready()
    first = _seconds()
    for p in PHASES:
        assert first[p] > before[p], p
    f(x).block_until_ready()
    assert _seconds() == first
    f(jnp.ones((25, 8))).block_until_ready()    # a new shape compiles again
    assert all(_seconds()[p] > first[p] for p in PHASES)


def test_the_listener_registers_once_however_often_it_is_asked(listening):
    def ours():
        return (sum(cb is compiles._on_duration for cb in
                    monitoring.get_event_duration_listeners()),
                sum(cb is compiles._on_event
                    for cb in monitoring.get_event_listeners()))

    assert ours() == (1, 1)
    for _ in range(3):
        enable_compile_cache()
        compiles.listen_to_compile_phases()
    assert ours() == (1, 1)


def test_the_counters_are_there_from_the_start_and_count_what_they_say(
        listening):
    reg = get_registry()
    for name in ("dl4j_compile_phase_seconds_total",
                 "dl4j_compile_cache_misses_total",
                 "dl4j_autotune_race_seconds_total"):
        assert reg.get(name) is not None, name
    seconds, misses, _ = compiles.phase_counters()
    before = seconds.value(phase="lower"), misses.value()
    compiles._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                          0.25)
    compiles._on_duration("/jax/core/some_other_duration", 9.0)
    compiles._on_event("/jax/compilation_cache/cache_misses")
    compiles._on_event("/jax/compilation_cache/cache_hits")
    assert seconds.value(phase="lower") == pytest.approx(before[0] + 0.25)
    assert misses.value() == before[1] + 1


def test_a_race_adds_its_wall_time(listening, monkeypatch, tmp_path):
    monkeypatch.setattr(autotune, "_CACHE_PATH", tmp_path / "autotune.json")
    monkeypatch.setattr(autotune, "_memory_cache", {})
    race = compiles.phase_counters()[2]
    before = race.value()
    made = []

    def make_run(cand):
        made.append(cand)
        return lambda: jnp.full((4,), float(cand[0])) * 2.0

    best = autotune.autotune("test_compile_phases:race", [(1,), (2,)],
                             make_run)
    assert best in [(1,), (2,)] and made == [(1,), (2,)]
    assert race.value() > before
    again = race.value()
    assert autotune.autotune("test_compile_phases:race", [(1,), (2,)],
                             make_run) == best      # the record answers
    assert race.value() == again
