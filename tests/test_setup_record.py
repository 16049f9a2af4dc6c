"""The set-up record (PR 38): ``observability.phase`` and the jax compile
listener, both feeding ``setup_record`` and the always-on
``process_registry``. The record and the registry are process-wide, so every
test reads what its own work added to them."""
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.observability import (monotonic, phase, process_registry,
                                      setup_record)
from paddle_tpu.observability import startup
from paddle_tpu.profiler.record import recorder

TINY = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64)
PHASES = ("weights.make", "weights.place", "kv.pools", "step.build")


def _model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(**TINY))
    model.eval()
    return model


def _predictor(model, **kw):
    from paddle_tpu.inference import ServingPredictor

    return ServingPredictor(model, max_batch=2, page_size=8, max_seq_len=64,
                            use_kernel=False, **kw)


def _since(t0):
    """What the record holds of work started at ``t0`` or later (by time:
    once the bounded record is full its length stays where it is)."""
    return [e for e in setup_record.entries() if e.start >= t0]


def _names(entries):
    return [e.name for e in entries]


def _set_up_counters():
    return {k: v for k, v in process_registry.snapshot_flat().items()
            if k.startswith(("setup_seconds", "jax_"))}


def test_each_phase_is_recorded_once_per_model_and_predictor():
    setup_record.settle()
    t0 = monotonic()
    model = _model()
    sp = _predictor(model)
    names = _names(_since(t0))
    assert [names.count(p) for p in PHASES] == [1, 1, 1, 1], names
    # the phases open on one thread nest: none was inside another here
    assert all(e.parent is None for e in _since(t0) if e.name in PHASES)
    assert sp.telemetry()["setup_seconds{phase=kv.pools}"] > 0


def test_each_phase_is_recorded_once_per_train_step_build():
    from jax.sharding import Mesh

    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "pp", "mp"))
    setup_record.settle()
    t0 = monotonic()
    step, params, mom, (ids, labels) = build_spmd_train_step(
        GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                  max_seq_len=32), mesh, batch_size=2, seq_len=32)
    # the step donates the weights the build made and waits for: the wait
    # for them ends either way, and the step runs
    params, mom, loss = step(params, mom, ids, labels)
    assert np.isfinite(float(loss))
    assert setup_record.settle()
    names = _names(_since(t0))
    assert [names.count(p) for p in PHASES] == [1, 1, 0, 1], names
    lowered = [e for e in _since(t0) if e.name == "jax.lower"]
    assert "step" in {e.fun for e in lowered}


def test_steps_after_ready_leave_the_record_and_counters_unchanged():
    sp = _predictor(_model())
    rng = np.random.RandomState(0)
    # warm: prefill and decode rows, the first step traces and compiles
    sp.add_request(rng.randint(0, TINY["vocab_size"], (20,)).tolist(),
                   max_new_tokens=40)
    for _ in range(4):
        sp.step()
    assert setup_record.settle()
    t0, before = monotonic(), _set_up_counters()
    for _ in range(20):
        sp.step()
    sp.flush()
    assert _since(t0) == []
    assert _set_up_counters() == before
    assert sp.decode_trace_count == 1


def test_a_nested_jit_is_counted_once_in_the_union():
    @jax.jit
    def inner_of_nested(x):
        return jnp.sin(x) * 2

    def outer_of_nested(x):
        return inner_of_nested(x) + 1

    before = _set_up_counters()
    t0 = monotonic()
    jax.jit(outer_of_nested)(jnp.ones(5))
    traces = [e for e in _since(t0) if e.name == "jax.trace"]
    inner, = [e for e in traces if e.fun == "inner_of_nested"]
    outer, = [e for e in traces if e.fun == "outer_of_nested"]
    assert outer.start <= inner.start <= inner.end <= outer.end
    after = _set_up_counters()
    added = {k: after[k] - before.get(k, 0.0) for k in after
             if k.startswith("jax_trace_seconds")}
    union = startup._Union()
    want = sum(union.add(e.start, e.end) for e in
               sorted(traces, key=lambda e: e.end))
    assert sum(added.values()) == pytest.approx(want, abs=1e-9)
    # the outer function is charged what the inner one did not cover
    own = added["jax_trace_seconds{fun=outer_of_nested}"]
    assert own < outer.end - outer.start
    assert own == pytest.approx((outer.end - outer.start)
                                - (inner.end - inner.start), abs=2e-3)


def test_a_second_shape_lowered_after_ready_raises_lowerings_by_one():
    def toy_ready_step(x):
        return x * 3

    fn = jax.jit(toy_ready_step)
    fn(jnp.ones(4))
    key = "jax_lowerings{fun=toy_ready_step}"
    assert process_registry.snapshot_flat()[key] == 1
    ready = monotonic()
    fn(jnp.ones(4))
    assert process_registry.snapshot_flat()[key] == 1
    t0 = monotonic()
    fn(jnp.ones(8))
    assert process_registry.snapshot_flat()[key] == 2
    late = [e for e in _since(t0) if e.fun == "toy_ready_step"]
    assert {e.name for e in late} >= {"jax.trace", "jax.lower",
                                      "jax.compile"}
    assert all(e.start >= ready for e in late)


def test_telemetry_carries_the_process_registry_and_stays_schema_shaped():
    from paddle_tpu.analysis.bench_schema import validate_line

    sp = _predictor(_model())
    sp.generate([[3, 1, 4, 1, 5]], max_new_tokens=3)
    flat = sp.telemetry()
    for p in PHASES:
        assert flat[f"setup_seconds{{phase={p}}}"] >= 0
    assert flat["jax_lowerings{fun=step}"] >= 1
    assert flat["serving_tokens_emitted"] == 3
    # the serving registry is the predictor's own, the rest is shared
    assert set(flat) == set(sp.metrics.snapshot_flat()) | set(
        process_registry.snapshot_flat())
    assert validate_line({"metric": "m", "value": 1.0, "unit": "tokens/s",
                          "telemetry": flat}) == []


def test_a_phase_writes_a_chrome_event_only_while_a_window_is_open():
    was = recorder.enabled
    recorder.clear()
    try:
        recorder.enabled = False
        t0 = monotonic()
        with phase("test.closed"):
            pass
        assert not [e for e in recorder.events if e.name == "test.closed"]
        recorder.enabled = True
        with phase("test.open"):
            with phase("test.inner"):
                pass
        got = {e.name: e for e in recorder.events}
        assert got["test.open"].category == "setup"
        assert got["test.inner"].start_ns >= got["test.open"].start_ns
    finally:
        recorder.enabled = was
        recorder.clear()
    # in the record either way, nested by thread
    mine = {e.name: e for e in _since(t0)}
    assert set(mine) == {"test.closed", "test.open", "test.inner"}
    assert mine["test.inner"].parent == "test.open"
    assert mine["test.open"].parent is None


def test_cache_events_go_to_the_backend_compile_that_closes_next():
    listener = startup._listener
    compile_ev = "/jax/core/compile/backend_compile_duration"
    now = time.time()
    listener.on_event("/jax/compilation_cache/cache_hits")
    listener.on_span(compile_ev, now - 0.2, now - 0.1,
                     fun_name="jit(toy_cached)")
    # another thread's miss is that thread's, not this compile's
    t = threading.Thread(target=listener.on_event,
                         args=("/jax/compilation_cache/cache_misses",))
    t.start()
    t.join(5)
    listener.on_span(compile_ev, now - 0.1, now, fun_name="jit(toy_cached)")
    listener.on_event("/jax/compilation_cache/cache_misses")
    listener.on_span(compile_ev, now, now + 0.1, fun_name="jit(toy_missed)")
    flat = process_registry.snapshot_flat()
    assert flat["jax_cache_hits{fun=toy_cached}"] == 1
    assert "jax_cache_misses{fun=toy_cached}" not in flat
    assert flat["jax_cache_misses{fun=toy_missed}"] == 1
    mine = [e for e in setup_record.entries()
            if e.fun in ("toy_cached", "toy_missed")][-3:]
    assert [e.cache for e in mine] == ["hit", None, "miss"]
    # on the record's clock: jax's time.time() shifted by one offset
    first = mine[0]
    assert first.end - first.start == pytest.approx(0.1, abs=1e-6)
    assert abs(first.end - (monotonic() - 0.1)) < 0.5


def test_a_phase_that_waits_for_its_arrays_ends_when_they_are_made():
    t0 = monotonic()
    before = process_registry.snapshot_flat().get(
        "setup_seconds{phase=test.made}", 0.0)
    with phase("test.made") as made:
        x = jax.random.normal(jax.random.key(1), (64, 64))
        made.end_when_ready(x)
        left = monotonic()
    assert setup_record.settle()
    mine, = [e for e in _since(t0) if e.name == "test.made"]
    assert mine.end >= left
    after = process_registry.snapshot_flat()["setup_seconds{phase=test.made}"]
    assert after - before == pytest.approx(mine.end - mine.start)


def test_phases_on_many_threads_lose_no_entry_and_no_second():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    t0 = monotonic()
    counter = process_registry.counter(
        "setup_seconds", labels=("phase",)).labels(phase="test.stress")
    before = counter.value

    def work():
        for _ in range(50):
            with phase("test.stress"):
                pass

    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    mine = [e for e in _since(t0) if e.name == "test.stress"]
    assert len(mine) == 16 * 50
    assert counter.value - before == pytest.approx(
        sum(e.end - e.start for e in mine))


@pytest.mark.parametrize("name, label", [
    ("step", "step"), ("jit(step)", "step"), ("pmap(f)", "f"),
    ("jit(<lambda>)", "<lambda>"), ("convert_element_type",
                                    "convert_element_type")])
def test_one_label_for_a_function_at_every_stage(name, label):
    assert startup.fun_label(name) == label


def test_the_union_charges_each_second_once():
    u = startup._Union()
    assert u.add(2.0, 3.0) == pytest.approx(1.0)
    assert u.add(2.5, 2.75) == 0.0                # inside: covered
    assert u.add(1.0, 4.0) == pytest.approx(2.0)  # around it: the rest
    assert u.add(5.0, 6.0) == pytest.approx(1.0)
    assert u.add(3.5, 5.5) == pytest.approx(1.0)  # bridges the gap
    assert u.add(0.0, 7.0) == pytest.approx(2.0)
