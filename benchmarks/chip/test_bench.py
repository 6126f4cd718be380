"""CPU checks of the benchmark's yardstick: traffic, end-to-end metrics,
work counts, the trace reduction, and the comparison that decides
``correct`` (its control, and faults planted under a run).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip
"""
from __future__ import annotations

import dataclasses
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec
import traffic
import work
from metrics import crossbar_mac_roofline
from peaks import peaks_for
from trace_reduce import union

HERE = Path(__file__).resolve().parent


def tiny_conf(config: str):
    """The configuration ``config`` cut to its family's test size."""
    conf = spec.load_json(HERE / "configs" / f"{config}.json")
    return dict(conf, **spec.family(conf).TINY)


def tiny_cell(config: str, mix: str, limits) -> spec.Cell:
    conf = tiny_conf(config)
    m = spec.load_json(HERE / "mixes" / f"{mix}.json")
    m = dict(m, prompt=dict(m["prompt"], max=min(m["prompt"]["max"], 40)),
             output=dict(m["output"], min=4, max=16))
    if m["loop"] == "closed":
        m["clients"] = 4
    serve = {"config": config, "mix": mix, "slots": 4, "max_len": 64,
             "chunk": 4, "page_size": 8, "rate_rps": 4.0,
             "limits": limits}
    return spec.Cell(f"{config}.{mix}", conf, m, serve,
                     [{"name": "tpot_p90_ms", "unit": "ms"}], [], 1)


# -- traffic -------------------------------------------------------------------

def test_open_loop_same_work_every_seed():
    mix = spec.load_json(HERE / "mixes" / "chat.json")
    a = traffic.open_loop(mix, 3.0, 45.0, 151936, seed=2 ** 31 + 5)
    b = traffic.open_loop(mix, 3.0, 45.0, 151936, seed=17)
    again = traffic.open_loop(mix, 3.0, 45.0, 151936, seed=17)
    assert len(a) == len(b) == 135
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt)
                                                      for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [p.max_new for p in a] != [p.max_new for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(b, again))
    assert 0 < b[0].due and b[-1].due < 45.0
    assert all(x.due < y.due for x, y in zip(b, b[1:]))
    assert all(8 <= len(p.prompt) <= 512 and 8 <= p.max_new <= 256
               for p in b)


def test_closed_loop_blocks_repeat_lengths():
    mix = spec.load_json(HERE / "mixes" / "decode.json")
    s = traffic.closed_loop(mix, 1000, seed=3)
    blocks = [[next(s) for _ in range(mix["clients"])] for _ in range(2)]
    lens = [sorted(p.max_new for p in b) for b in blocks]
    assert lens[0] == lens[1]
    assert all(16 <= p.max_new <= 512 for p in blocks[0])


def test_closed_loop_drains_to_first_token():
    """After the close a closed loop sends nothing more and steps on
    until every request sent in the window has its first token, so the
    wait for the first token is read over all of them."""
    import drive
    from repro.models.model import build_model
    from repro.serve.engine import BatchScheduler
    cell = tiny_cell("qwen3-4b", "decode", TINY_LIMITS["qwen3-4b"])
    cfg = spec.model_config(cell.config)
    model = build_model(cfg)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=4, max_len=64, chunk=4)
    drv = drive.Driver(sched)
    stream = traffic.closed_loop(cell.mix, cfg.vocab, seed=4)
    drive.start_closed(drv, stream, cell.mix["clients"])
    t_open = drive.clock()
    t_close = drive.run_closed(drv, stream, t_open, 0.5, drain_s=60.0)
    sent = [tr for tr in drv.tracks if t_open <= tr.due < t_close]
    assert sent and all(tr.tokens for tr in sent)
    # the drain stopped as soon as the last of them had its first token
    late = [s for s in drv.steps if s.t0 >= t_close]
    assert not late or max(tr.tokens[0] for tr in sent) == late[-1].t1


def test_every_cell_fits_its_cache():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        worst = cell.mix["prompt"]["max"] + cell.mix["output"]["max"] - 1
        assert worst <= cell.serve["max_len"], w["name"]
        assert cell.serve["max_len"] % cell.serve["page_size"] == 0


def test_end_to_end_over_the_window():
    """Hand-made records: two requests, one due inside the window."""
    import types

    import endtoend
    from drive import Track
    mk = types.SimpleNamespace
    a = Track(plan=None, req=None, due=10.0, tokens=[10.5, 11.0, 11.25,
                                                     12.5])
    b = Track(plan=None, req=None, due=8.0, tokens=[9.0, 10.5])
    run = mk(drv=mk(tracks=[a, b]), t_open=10.0, t_close=12.0, t_start=1.0,
             window_tracks=[a])
    assert endtoend.ttft_p90_ms(run) == pytest.approx(500.0)
    # gaps inside [10, 12]: a's 0.5 and 0.25 (b's ends inside but starts
    # before the window; a's last ends after it)
    assert endtoend.tpot_p90_ms(run) == pytest.approx(
        float(np.percentile([500.0, 250.0], 90)))
    assert endtoend.output_tok_s(run) == pytest.approx(4 / 2.0)
    assert endtoend.setup_s(run) == 9.0


# -- peaks and work ------------------------------------------------------------

def test_peaks_unknown_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_step_work_hand_counted():
    conf = spec.load_json(HERE / "configs" / "qwen3-4b.json")
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560,
    # gate and up 2560x9728, down 9728x2560
    per_layer = (2560 * 4096 * 2 + 2560 * 1024 * 2 + 2560 * 9728 * 3)
    family = spec.family(conf)
    assert family.trunk_weights(conf) == 19 * per_layer
    assert family.head_weights(conf) == 2560 * 151936
    # one decode token at context 100
    flops = work.step_flops(conf, positions=1, context=100, tokens=1)
    assert flops == (2 * 19 * per_layer + 2 * 2560 * 151936
                     + 19 * 4 * 32 * 128 * 100)
    nbytes = work.step_bytes(conf, positions=1, context=100)
    kv = 2 * 8 * 128 * 2 * 19
    assert nbytes == 2 * (19 * per_layer + 2560 * 151936) + kv * 101


def test_crossbar_work_hand_counted():
    conf = spec.load_json(HERE / "configs" / "qwen3-4b-xbar.json")
    per_layer = (2560 * 4096 * 2 + 2560 * 1024 * 2 + 2560 * 9728 * 3)
    head = 2560 * 151936
    ops, nbytes = crossbar_mac_roofline.step_ops_bytes(conf, positions=3,
                                                       tokens=2)
    # 8 pulses x 4 slices x (pos + neg) x 2 ops per multiply-add
    assert ops == 128 * (2 * per_layer * 3 + head * 2)
    cols = 2 * (4096 + 1024 * 2 + 2560 + 9728 * 2 + 2560) + 151936
    ins = 2 * (2560 * 3 + 4096 + 2560 * 2 + 9728)
    weights_b = 2 * per_layer + head                 # 8 bits per weight
    io = 3 * (ins + 4 * (cols - 151936)) + 2 * (2560 + 4 * 151936)
    assert nbytes == weights_b + 4 * cols + io


# -- model families ------------------------------------------------------------

def _sha256(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# sha256 of each leaf's path and bytes of the test-size parameters at seed
# 11 (the program's tree, then its published view), as the harness drew
# them when Qwen3 was written into its generic files: the family plug-in
# has to give the same weights, bit for bit, so that no reading moves.
PARAMS_SHA256 = {
    "qwen3-4b": (
        "862a2832884111676bf4bbb6ab00f92f0a06915d8eaac3bb9cc08def332c7247",
        "ee69b9a52f9c5f3fd898c87f5f9ab6c43db8f18b4a174840ce3ada79fddf044e"),
    "qwen3-4b-xbar": (
        "a689e79967d7a0c5c6e5279b2553550dc17e9342cc407a1939a69fcbda72d676",
        "16b0ca9b2f5b882ad1f073c2880b08f7cb08a9ccda71952e07858f1c576c6b9b"),
}


@pytest.mark.parametrize("config", sorted(PARAMS_SHA256))
def test_family_params_bit_identical(config):
    import weights
    conf = tiny_conf(config)
    params = weights.make_params(conf, 11)
    assert (_sha256(params), _sha256(weights.published(conf, params))
            ) == PARAMS_SHA256[config]


def test_unknown_family_is_an_error():
    conf = spec.load_json(HERE / "configs" / "qwen3-4b.json")
    with pytest.raises(ValueError, match=r"families/no_such_model\.py"):
        spec.family(dict(conf, reference="no_such_model"))
    conf.pop("reference")
    with pytest.raises(ValueError, match=r"families/<reference>\.py"):
        spec.model_config(conf)


# A family that lives outside the benchmark's directory: Qwen3 under
# another name, recording every call the harness makes into it.
ELSEWHERE = '''
import families.qwen3 as base

CALLS = []
TINY = base.TINY


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(base, name)(*args, **kwargs)
    return call


program_config, make_params, published, logits_at, linears, step_flops, \\
    step_bytes = map(_recorded, ("program_config", "make_params",
                                 "published", "logits_at", "linears",
                                 "step_flops", "step_bytes"))
'''


def test_configuration_brings_its_own_family(tmp_path, monkeypatch):
    """A configuration whose ``reference`` names a plug-in found only in
    another directory runs a whole cell through it, and the work counts
    the metric readers take come from it too."""
    import sys
    import types

    import families
    import run as R
    from drive import Step
    from metrics import step_mfu, step_roofline
    (tmp_path / "qwen3_elsewhere.py").write_text(ELSEWHERE)
    monkeypatch.setattr(families, "__path__",
                        [str(tmp_path), *families.__path__])
    monkeypatch.delitem(sys.modules, "families.qwen3_elsewhere",
                        raising=False)
    cell = tiny_cell("qwen3-4b", "chat_short", TINY_LIMITS["qwen3-4b"])
    conf = dict(cell.config, reference="qwen3_elsewhere")
    out = R.run_cell(dataclasses.replace(cell, config=conf), seed=12,
                     seconds=2.0, trace=False)
    plugin = sys.modules["families.qwen3_elsewhere"]
    assert out["correct"] is True, out["checks"]
    assert {"program_config", "make_params", "published",
            "logits_at"} <= set(plugin.CALLS)

    steps = [Step(0.0, 0.05, positions=7, context=90, tokens=4)]
    xbar = spec.load_json(HERE / "configs" / "qwen3-4b-xbar.json")
    reads = []
    for c in (conf, cell.config):
        run = types.SimpleNamespace(
            conf=c, window_steps=steps, traced_steps=steps,
            peaks=peaks_for("TPU v5 lite"),
            trace=types.SimpleNamespace(step_busy_s=[0.04]))
        reads.append((step_mfu.read(run), step_roofline.read(run),
                      crossbar_mac_roofline.step_ops_bytes(
                          dict(c, crossbar=xbar["crossbar"]), positions=7, tokens=4)))
    assert reads[0] == reads[1]
    assert {"linears", "step_flops", "step_bytes"} <= set(plugin.CALLS)


# -- trace reduction -----------------------------------------------------------

def test_union_merges_overlaps():
    iv = union(np.array([0.0, 1.0, 5.0, 2.0]), np.array([1.5, 3.0, 6.0,
                                                          2.5]))
    assert iv == [(0.0, 3.0), (5.0, 6.0)]


def test_reduce_handmade_trace():
    """A hand-made trace with known answers (testdata/handmade.xspace.txt,
    times in microseconds)."""
    from jax.profiler import ProfileData
    from trace_reduce import reduce_profile
    txt = (HERE / "testdata" / "handmade.xspace.txt").read_text()
    r = reduce_profile(ProfileData.from_text_proto(txt))
    us = 1e-6
    assert r.window == pytest.approx((100 * us, 1100 * us))
    # 150-380, 550-790 (copy.2 nests inside fusion.1), 950-1000; the op
    # at 50-90 lies before the stretch
    assert r.busy_s == pytest.approx(520 * us)
    assert r.step_busy_s == pytest.approx([230 * us, 240 * us, 50 * us])
    assert r.op_seconds(crossbar_mac_roofline.KERNEL) == pytest.approx(
        170 * us)
    assert dict(r.top_ops()) == pytest.approx(
        {"fusion.1": 350 * us, "crossbar_mac.3": 170 * us, "copy.2": 50 * us})
    # gap 380-550 is mostly under sched.step (20 + 50 us), 790-950 under
    # client_bookkeeping (50 us against 10 + 10), 100-150 and 1000-1100
    # under sched.step
    assert dict(r.idle_gaps) == pytest.approx(
        {"sched.step": 320 * us, "client_bookkeeping": 160 * us})


def test_reduce_recorded_trace():
    """A v5e trace of a tiny crossbar cell (testdata/small.xplane.pb, one
    chip, 0.3 s of steps): busy inside the stretch, per-step busy summing
    to no more than it, ``crossbar_mac`` found by the op name the chip
    prints, and every idle second charged to a host span."""
    from trace_reduce import reduce_xspace
    r = reduce_xspace(str(HERE / "testdata" / "small.xplane.pb"))
    assert 0 < r.busy_s < r.window_s
    assert len(r.step_busy_s) >= 10
    assert sum(r.step_busy_s) <= r.busy_s * (1 + 1e-9)
    kernel = r.op_seconds(crossbar_mac_roofline.KERNEL)
    assert 0 < kernel <= r.busy_s
    idle = sum(s for _, s in r.idle_gaps)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert r.top_ops()[0][0].startswith("%crossbar_mac.")


# -- the reference -------------------------------------------------------------

def test_crossbar_reference_matches_program_linear():
    """The plain crossbar read agrees with the program's own reference
    scan (two independent writings of one arithmetic) on a weight whose
    K spans several ADC groups."""
    from repro.core import engine
    from repro.core.engine import EngineConfig
    conf = spec.load_json(HERE / "configs" / "qwen3-4b-xbar.json")
    arith = reference.arith_for(conf)
    w = jax.random.normal(jax.random.PRNGKey(0), (300, 72)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 300))
    cfg = EngineConfig(mode="deepnet")
    want = engine.matmul_reference(x, engine.program(w, cfg), cfg)
    got = reference.linear(x, w, arith)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale


# Limits for the test size, set from its readings (seeds 11-13 on the
# CPU): digital program widest 0.004-0.036, mean <= 0.0002, int8 widest
# 0.051-0.074, mean 0.0007-0.0022, fp8 widest >= 0.29; crossbar program
# widest 0.10-0.16, mean <= 0.006, 4-input-bit control widest >= 1.6,
# mean >= 0.24.
TINY_LIMITS = {"qwen3-4b": {"widest_gap": 0.2, "mean_gap": 0.0005},
               "qwen3-4b-xbar": {"widest_gap": 0.8, "mean_gap": 0.05}}


@pytest.mark.parametrize("config", ["qwen3-4b", "qwen3-4b-xbar"])
def test_control_fails_the_comparison(config):
    """At a test size, in one run: the program reads correct, and each
    control (a precision below the configuration's), judged by the same
    checks with its own tokens in place of the served ones, does not."""
    import run as R
    cell = tiny_cell(config, "chat_short", TINY_LIMITS[config])
    names = tuple(reference.controls_for(cell.config))
    out = R.run_cell(cell, seed=11, seconds=3.0, trace=False,
                     controls=names)
    assert out["info"]["tokens_compared"] > 50
    assert out["correct"] is True, out["checks"]
    assert set(out["controls"]) == set(names)
    for name, ctl in out["controls"].items():
        assert ctl["correct"] is False, (name, ctl["checks"])


def _broken(kind):
    from repro.serve import engine as E
    real_make = E.make_window_step

    def make(model, tenant="A"):
        real = real_make(model, tenant)
        vocab = model.cfg.vocab

        @jax.jit
        def step(params, tiles, tokens, cache, m, leak):
            tok, new = real(params, tiles, tokens, cache, m, leak)
            if kind == "token":
                tok = (tok + 1) % vocab
            else:   # the layers' state comes back unchanged
                new = dict(new, layers=cache["layers"])
            return tok, new
        return step
    return make


@pytest.mark.parametrize("fault", ["token", "state"])
@pytest.mark.parametrize("config,mix", [("qwen3-4b", "chat_short"),
                                        ("qwen3-4b-xbar", "decode"),
                                        ("qwen3-4b", "decode")])
def test_planted_fault_reads_incorrect(monkeypatch, fault, config, mix):
    """A whole run, chip check aside, with the timed path broken under
    it: a token altered where the step produces it, or a step that
    returns its KV state unchanged, has to come out not correct."""
    import run as R
    from repro.serve import engine as E
    cell = tiny_cell(config, mix, TINY_LIMITS[config])
    monkeypatch.setattr(E, "make_window_step", _broken(fault))
    out = R.run_cell(cell, seed=12, seconds=2.0, trace=False)
    assert out["info"]["tokens_compared"] > 0
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("config,mix", [("qwen3-4b", "chat_short"),
                                        ("qwen3-4b-xbar", "decode"),
                                        ("qwen3-4b", "decode")])
def test_sound_run_reads_correct(config, mix):
    import run as R
    out = R.run_cell(tiny_cell(config, mix, TINY_LIMITS[config]), seed=12,
                     seconds=2.0, trace=False)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0
