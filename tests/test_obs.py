"""Observability layer: metrics registry + tracer unit behaviour, and
the serving/executor integration invariants the telemetry smoke gates
on — span decomposition, retrace counters across hot-swap windows, and
device-energy accounting parity with ``core/timing.py``."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import engine, timing
from repro.core.engine import EngineConfig
from repro.core.executor import CrossbarExecutor
from repro.core.quant import QuantConfig
from repro.models.model import ModelConfig, build_model
from repro.obs import MetricsRegistry, Tracer, parse_prometheus
from repro.serve.engine import BatchScheduler, Request
from repro.serve.hotswap import finetune_delta

TINY = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv=2, head_dim=16, d_ff=64, vocab=128, backend="crossbar",
    dtype=jnp.float32,
    xbar=EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                      quant=QuantConfig(w_bits=4, in_bits=6, adc_bits=12)))

DIGITAL = ModelConfig(
    name="tiny-digital", family="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv=2, head_dim=16, d_ff=64, vocab=128,
    dtype=jnp.float32)


def _submit(sched, model_id, n_req, max_new=4, seed0=0):
    for i in range(n_req):
        p = jax.random.randint(jax.random.PRNGKey(seed0 + i), (5,), 0,
                               TINY.vocab - 1).astype(jnp.int32)
        sched.submit(Request(rid=seed0 + i, prompt=p, max_new=max_new,
                             model_id=model_id))


def _drain(sched, n_req, max_steps=200):
    done, steps = [], 0
    while len(done) < n_req and steps < max_steps:
        done += sched.step()
        steps += 1
    return done


# -- registry unit behaviour --------------------------------------------------

def test_histogram_bucket_edges():
    """Prometheus bucket semantics: an observation lands in every bucket
    with value <= le, and +Inf equals the total count."""
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 2.0, 5.0001):
        h.observe(v)
    assert h.bucket_counts() == {"1.0": 2, "2.0": 3, "5.0": 3, "+Inf": 4}
    assert h.get_count() == 4
    assert h.get_sum() == pytest.approx(8.5001)
    # layout is part of the metric identity
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("lat", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("bad", buckets=(2.0, 1.0))


def test_counter_labels_and_total_filtering():
    reg = MetricsRegistry()
    c = reg.counter("reads")
    c.inc(2.0, tenant="A", mode="deepnet")
    c.inc(3.0, tenant="A", mode="expansion")
    c.inc(5.0, tenant="B", mode="deepnet")
    assert reg.get("reads", tenant="A", mode="deepnet") == 2.0
    assert reg.total("reads", tenant="A") == 5.0
    assert reg.total("reads", mode="deepnet") == 7.0
    assert reg.total("reads") == 10.0
    assert reg.total("no_such_metric") == 0.0
    with pytest.raises(ValueError, match="monotone"):
        c.inc(-1.0)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reads")


def test_disabled_registry_and_tracer_are_noops():
    reg = MetricsRegistry(enabled=False)
    reg.counter("c").inc(5.0)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(0.1)
    assert reg.total("c") == 0.0
    assert reg.get("g") == 0.0
    assert reg.histogram("h").get_count() == 0
    tr = Tracer(enabled=False)
    assert tr.record("x", 0.0, 1.0) is None
    assert len(tr.spans()) == 0
    assert isinstance(tr.now(), float)   # clock stays usable


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("hits", help="hit count").inc(3.0, path='k"er\\nel')
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05, tenant="A")
    h.observe(0.5, tenant="A")
    samples = parse_prometheus(reg.to_prometheus())
    by = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
          for s in samples}
    assert by[("hits", (("path", 'k"er\\nel'),))] == 3.0
    assert by[("depth", ())] == 2.5
    assert by[("lat_bucket", (("le", "0.1"), ("tenant", "A")))] == 1.0
    assert by[("lat_bucket", (("le", "+Inf"), ("tenant", "A")))] == 2.0
    assert by[("lat_count", (("tenant", "A"),))] == 2.0
    assert by[("lat_sum", (("tenant", "A"),))] == pytest.approx(0.55)
    with pytest.raises(ValueError):
        parse_prometheus("this is { not a metric line")


def test_jsonl_export_round_trip():
    reg = MetricsRegistry()
    reg.counter("c").inc(2.0, tenant="A")
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    tr = Tracer()
    tr.record("request", 1.0, 3.5, rid=7, tenant="A")
    docs = [json.loads(line) for line in
            (reg.to_jsonl() + tr.to_jsonl()).splitlines()]
    kinds = {d["kind"] for d in docs}
    assert kinds == {"metric", "span"}
    span = next(d for d in docs if d["kind"] == "span")
    assert span["span"] == "request"
    assert span["duration_s"] == pytest.approx(2.5)
    assert span["attr_rid"] == 7
    metric = next(d for d in docs if d.get("metric") == "c")
    assert metric["value"] == 2.0
    assert metric["labels"] == {"tenant": "A"}


def test_tracer_span_filters():
    tr = Tracer()
    tr.record("decode", 0.0, 1.0, rid=1, tenant="A")
    tr.record("decode", 0.0, 2.0, rid=2, tenant="B")
    tr.record("request", 0.0, 3.0, rid=1, tenant="A")
    assert len(tr.spans("decode")) == 2
    assert len(tr.spans(tenant="A")) == 2
    assert tr.spans("decode", rid=2)[0].duration == pytest.approx(2.0)
    tr.clear()
    assert len(tr) == 0


# -- scheduler integration ----------------------------------------------------

def test_request_span_decomposition_sums_to_wall_time():
    """queue_wait + prefill + decode telescope exactly to the request
    span, and the TTFT attribute is submit-to-first-token."""
    model = build_model(DIGITAL)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=2, max_len=24)
    _submit(sched, "A", 3, max_new=4)
    done = _drain(sched, 3)
    assert len(done) == 3
    for r in done:
        parts = {name: sched.tracer.spans(name, rid=r.rid)
                 for name in ("queue_wait", "prefill", "decode",
                              "request")}
        assert all(len(v) == 1 for v in parts.values())
        req = parts["request"][0]
        decomp = sum(parts[n][0].duration
                     for n in ("queue_wait", "prefill", "decode"))
        assert decomp == pytest.approx(req.duration, abs=1e-9)
        assert req.attrs["ttft_s"] == pytest.approx(
            parts["queue_wait"][0].duration
            + parts["prefill"][0].duration, abs=1e-9)
        assert req.attrs["n_tokens"] == len(r.out)
    # the registry agrees with the tracer
    m = sched.metrics
    assert m.total("serve_requests_submitted_total") == 3
    assert m.total("serve_requests_completed_total") == 3
    assert m.histogram("serve_ttft_seconds").get_count(tenant="A") == 3


def test_telemetry_off_scheduler_still_serves():
    model = build_model(DIGITAL)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=2, max_len=24, telemetry=False)
    _submit(sched, "A", 2, max_new=3)
    done = _drain(sched, 2)
    assert len(done) == 2
    assert len(sched.tracer.spans()) == 0
    assert sched.metrics.total("serve_tokens_total") == 0.0
    # lane accounting stays authoritative with metrics off
    assert sched.qos_report()["A"]["tokens_served"] >= 6


def test_retrace_counter_zero_across_hot_swap_window():
    """The runtime form of the no-retrace invariant: a tenant-B swap
    under traffic must not bump serve_jit_retraces_total."""
    model = build_model(TINY)
    params_a = model.init(jax.random.PRNGKey(0))
    params_b = finetune_delta(params_a, scale=0.05, seed=7)
    sched = BatchScheduler(model, params_a, n_slots=2, max_len=24,
                           tenants={"A": params_a, "B": params_b})
    _submit(sched, "A", 2, max_new=8, seed0=0)
    _submit(sched, "B", 1, max_new=3, seed0=200)
    done = []
    for _ in range(2):
        done += sched.step()
    reg = obs.registry()
    before = reg.total("serve_jit_retraces_total")
    sched.begin_hot_swap(finetune_delta(params_a, scale=0.08, seed=23),
                         chunks_per_step=6, tenant="B")
    steps = 0
    while (sched.swap_in_flight or len(done) < 3) and steps < 200:
        done += sched.step()
        steps += 1
    assert len(done) == 3
    assert reg.total("serve_jit_retraces_total") == before
    # the window itself was recorded
    assert sched.metrics.total("serve_swap_windows_total",
                               tenant="B", policy="overlapped") == 1
    assert len(sched.tracer.spans("swap_window", tenant="B")) == 1


def test_retrace_counter_increments_on_forced_retrace():
    """Calling a decode closure at a new batch shape IS a re-trace, and
    the counter sees it — the signal the invariant gates on."""
    model = build_model(DIGITAL)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=2, max_len=24)
    _submit(sched, "A", 2, max_new=3)
    _drain(sched, 2)
    reg = obs.registry()
    before = reg.total("serve_jit_retraces_total", closure="decode")
    lane = sched._lanes["A"]
    # batch-of-1 call against the width-traced window closure: new
    # shape, same built closure -> jit re-traces it
    lane.decode(lane.params, {}, jnp.zeros((1, sched.chunk), jnp.int32),
                model.init_cache(1, 24), jnp.ones((1,), jnp.int32),
                jnp.float32(0.0))
    after = reg.total("serve_jit_retraces_total", closure="decode")
    assert after == before + 1


def test_device_energy_accounting_matches_timing_model():
    """device_token_cost is the Table-I model of core/timing.py, and the
    serving counters accumulate exactly cost x tokens served."""
    cfg = TINY.xbar
    q, p = cfg.quant, cfg.params
    w = jax.random.normal(jax.random.PRNGKey(1), (48, 40)) * 0.3
    ex = CrossbarExecutor(cfg)
    ex.program_params({"head": w})
    cost = ex.device_token_cost()
    assert list(cost) == ["deepnet"]
    s, t, r, n_pad = (int(d) for d in
                      ex._cache["head"].active_for("A").pos.shape)
    assert cost["deepnet"]["read_s"] == pytest.approx(
        timing.read_time(q.in_bits, p))
    assert cost["deepnet"]["energy_j"] == pytest.approx(
        q.in_bits * s * t * 2 * timing.mac_energy(r, n_pad, p=p))

    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=2, max_len=24)
    _submit(sched, "A", 2, max_new=4)
    _drain(sched, 2)
    tokens = sched._lanes["A"].tokens_served
    assert tokens > 0
    c = model.executor.device_token_cost("A")["deepnet"]
    assert sched.metrics.total("serve_device_energy_joules_total",
                               tenant="A", mode="deepnet"
                               ) == pytest.approx(c["energy_j"] * tokens,
                                                  rel=1e-9)
    assert sched.metrics.total("serve_device_read_seconds_total",
                               tenant="A", mode="deepnet"
                               ) == pytest.approx(c["read_s"] * tokens,
                                                  rel=1e-9)
    # mode_report's traffic block is the same registry view
    traffic = sched.mode_report()["traffic"]
    assert traffic["tokens_served"] == tokens
    assert traffic["modes"]["deepnet"]["pj_per_token"] == pytest.approx(
        c["energy_j"] * 1e12, rel=1e-9)


def test_mode_report_defaults_to_anchor_and_names_tenants_on_miss():
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=2, max_len=24)
    # no arg = the executor's anchor tenant (what sched.params serves)
    assert sched.mode_report() == sched.mode_report("A")
    with pytest.raises(KeyError, match=r"no lane for tenant 'Z'.*\['A'\]"):
        sched.mode_report("Z")


def test_path_calls_registry_view_is_dict_compatible():
    view = engine.path_calls
    assert set(dict(view)) == {"kernel", "reference"}
    assert view == dict(view)              # both comparison directions
    assert dict(view) == view
    with pytest.raises(KeyError):
        view["no_such_path"]
    before = view["reference"]
    cfg = EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                       quant=QuantConfig(w_bits=4, in_bits=6,
                                         adc_bits=12))
    w = jax.random.normal(jax.random.PRNGKey(2), (40, 24)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40))
    engine.matmul_reference(x, engine.program(w, cfg), cfg)
    assert view["reference"] == before + 1
    # per-geometry labels ride the registry sample
    assert obs.registry().get("crossstack_dispatch_total",
                              path="reference", geometry="40x24") >= 1


# -- serve.* host spans and the window counters ------------------------------

BENCH_SPANS = {"sched.step", "arrivals", "submit", "client_bookkeeping",
               "bench.traced"}


def _traced_step_spans(sched, tmp_path):
    """``serve.*`` host span names of one ``step()`` under the profiler,
    in start order, and every host event name seen."""
    import glob

    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        sched.step()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(ev.start_ns, ev.name)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    names = {n for _, n in events}
    return [n for _, n in sorted(events) if n.startswith("serve.")], names


@pytest.mark.parametrize("swap", [False, True])
def test_step_emits_serve_spans_in_order(tmp_path, swap):
    """One step under the profiler: the scheduler's phase spans, in
    order (``serve.swap`` only while a swap is in flight), and none of
    the benchmark client's span names."""
    model = build_model(TINY if swap else DIGITAL)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=2, max_len=24)
    _submit(sched, "A", 2, max_new=8)
    sched.step()                          # compile outside the trace
    if swap:
        sched.begin_hot_swap(finetune_delta(params, scale=0.05, seed=3),
                             chunks_per_step=1)
    spans, names = _traced_step_spans(sched, tmp_path)
    want = ["serve.step", "serve.admit", "serve.window", "serve.dispatch",
            "serve.sync", "serve.emit"]
    if swap:
        want.insert(1, "serve.swap")
    assert spans == want
    assert not names & BENCH_SPANS


def test_telemetry_off_emits_no_serve_spans(tmp_path):
    model = build_model(DIGITAL)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=2, max_len=24, telemetry=False)
    _submit(sched, "A", 2, max_new=8)
    sched.step()
    spans, _ = _traced_step_spans(sched, tmp_path)
    assert spans == []


def test_window_counters_match_request_state():
    """Step by step, the window and head counters move by what the
    requests' own state says the step did: useful positions = prompt
    tokens fed + decoding rows, emitted = tokens handed out, computed =
    width x chunk for both, and the head reads one row per slot."""
    model = build_model(DIGITAL)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=3, max_len=32, chunk=4)
    reqs = [Request(rid=i, max_new=2 + 3 * i,
                    prompt=jnp.arange(3 + 5 * i, dtype=jnp.int32) % 100)
            for i in range(4)]
    for r in reqs:
        sched.submit(r)
    reg = sched.metrics

    def counts():
        return (reg.total("serve_window_positions_total", kind="useful"),
                reg.total("serve_window_positions_total", kind="computed"),
                reg.total("serve_head_rows_total", kind="emitted"),
                reg.total("serve_head_rows_total", kind="computed"))

    steps = 0
    while not all(r.done for r in reqs):
        before = [(r.fed, r.feed is not None and r.fed >= len(r.feed),
                   len(r.out)) for r in reqs]
        c0 = counts()
        read0 = reg.total("serve_head_rows_total", kind="read")
        sched.step()
        steps += 1
        useful = tokens = 0
        for r, (fed0, decoding, nout0) in zip(reqs, before):
            if r.fed > fed0:
                useful += r.fed - fed0
            elif decoding and len(r.out) > nout0:
                useful += 1
            tokens += len(r.out) - nout0
        delta = [b - a for a, b in zip(c0, counts())]
        assert delta == [useful, 3 * 4, tokens, 3 * 4], steps
        assert reg.total("serve_head_rows_total",
                         kind="read") - read0 == 3, steps
    assert counts()[2] == sum(r.max_new for r in reqs)
