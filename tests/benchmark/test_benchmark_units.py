"""The yardstick's own arithmetic, on the CPU: traffic is a function of the
seed, metrics follow from recorded client records, the manifest agrees
with the files, the roofline functions agree with hand arithmetic, the
trace reduction agrees with a recorded trace, and the two hooks into the
program are where serve_cell.py expects them."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (loadgen, manifest, metrics, roofline,  # noqa: E402
                       trace_reduce, traffic)

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat-steady", "chat-backlog"])
def test_one_seed_one_traffic(mix):
    with open(os.path.join(ROOT, "benchmark", "traffic", mix + ".json")) as f:
        tr = dict(json.load(f), rate_rps=7.0)
    a = [traffic.make_session(tr, 11, i) for i in range(50)]
    b = [traffic.make_session(tr, 11, i) for i in range(50)]
    c = [traffic.make_session(tr, 12, i) for i in range(50)]
    assert a == b and a != c
    assert traffic.arrival_times(tr, 11, 30.0) == \
        traffic.arrival_times(tr, 11, 30.0)
    # A session is the same whichever rate it arrives at.
    assert a == [traffic.make_session(dict(tr, rate_rps=3.0), 11, i)
                 for i in range(50)]
    head, tail = tr["prompt"]["head"], tr["prompt"]["tail"]
    for s in a:
        (turn,) = s.turns
        body = turn.prompt[len(head):-len(tail)]
        assert turn.prompt.startswith(head) and turn.prompt.endswith(tail)
        assert 32 <= len(body) <= 1500 and 16 <= turn.num_predict <= 256
        assert len(turn.prompt.encode()) == len(turn.prompt)  # a byte a char
    assert len({s.turns[0].prompt[len(head):] for s in a}) == 50


def test_traffic_head_is_the_programs_template():
    from p2p_llm_chat_tpu.serve.engine import SUGGEST_PREFIX
    for mix in ("chat-steady", "chat-backlog"):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               mix + ".json")) as f:
            assert json.load(f)["prompt"]["head"] == SUGGEST_PREFIX


@pytest.mark.parametrize("arrivals,session", [
    ({"process": "poisson"}, None),
    ({"process": "bursts", "size": [16, 64], "within_s": 0.2}, None),
    ({"process": "poisson"}, {"turns": [4, 8], "system_tokens": 1024,
                              "think_s": [2, 5]}),
])
def test_generators_for_the_mixes_kept_for_later(arrivals, session):
    """chat-bursty and sessions-shared (PERF.md) need data files only."""
    tr = {"loop": "open", "rate_rps": 8.0, "arrivals": arrivals,
          "prompt": {"head": "H:", "tail": ":T", "body_tokens": {
              "dist": "uniform", "min": 100, "max": 300}},
          "output_tokens": {"dist": "fixed", "value": 64}}
    if session:
        tr["session"] = session
    times = traffic.arrival_times(tr, 3, 2000.0)
    assert times == sorted(times) and all(0 <= t < 2000 for t in times)
    assert 0.8 * 16000 < len(times) < 1.2 * 16000   # the mean rate holds
    s = traffic.make_session(tr, 3, 0)
    if session:
        assert 4 <= len(s.turns) <= 8
        for a, b in zip(s.turns, s.turns[1:]):
            # Each turn's prompt extends the one before: a shared prefix.
            assert b.prompt.startswith(a.prompt[:-2]) and 2 <= b.think_s <= 5
        other = traffic.make_session(tr, 3, 1)
        assert other.turns[0].prompt[:1026] == s.turns[0].prompt[:1026]
    else:
        assert len(s.turns) == 1


# -- metric arithmetic --------------------------------------------------------

def _rec(due, send, chunks, end, ok=True, **kw):
    r = loadgen.Record(session=0, turn=0, due_t=due, prompt_bytes=100,
                       num_predict=sum(n for _, n in chunks), **kw)
    r.send_t = send
    r.status = 200 if ok else 503
    r.chunk_t = [t for t, _ in chunks]
    r.chunk_tokens = [n for _, n in chunks]
    r.end_t = end if ok else None
    return r


def test_metric_arithmetic_on_recorded_records():
    recs = [
        # due 5.0, sent 20 ms late, first chunk carries 3 tokens
        _rec(5.0, 5.02, [(5.12, 3), (5.14, 1), (5.20, 4)], 5.21),
        _rec(6.0, 6.0, [(6.30, 1), (6.32, 1), (6.34, 1)], 6.35),
        _rec(7.0, 7.0, [], None, ok=False),          # shed: a miss
        _rec(2.0, 2.0, [(2.1, 1), (5.5, 1)], 5.6),   # due in the ramp
        _rec(16.0, 16.0, [(16.1, 1), (16.2, 1)], 16.3),  # due after
    ]
    obs = metrics.Observations(recs, ramp_s=5.0, window_s=10.0)
    assert metrics.counts(obs) == (3, 1)
    assert metrics.ttft_ms(recs[0]) == pytest.approx(120.0)   # from due
    assert metrics.ttft_from_send_ms(recs[0]) == pytest.approx(100.0)
    # (5.20 - 5.12) / (8 - 3 tokens)
    assert metrics.tpot_ms(recs[0]) == pytest.approx(16.0)
    assert metrics.tpot_ms(recs[1]) == pytest.approx(20.0)
    assert metrics.ttft_ms(recs[2]) is None
    e = metrics.end_to_end(obs)
    assert e["ttft_p50_ms"] == pytest.approx(120.0)   # nearest rank of 2
    assert e["ttft_p95_ms"] == pytest.approx(300.0)
    # tokens by the time they were read: 8 + 3 + the ramp request's late 1
    assert obs.tokens_in_window() == 12
    assert e["out_tok_s"] == pytest.approx(1.2)
    # per token after the first chunk: 20/1, 60/4 four times; 20, 20
    assert metrics.token_gaps_ms(recs[0]) == pytest.approx([20.0] + [15.0] * 4)
    assert e["itl_p50_ms"] == pytest.approx(15.0)   # 4th of 7, pooled
    read = lambda m: manifest.load_reader(
        os.path.join(ROOT, "benchmark"), m)(obs)
    assert read("slo_share") == pytest.approx(100.0 * 2 / 3)
    assert read("gen_lag_p99_ms") == pytest.approx(20.0)
    assert read("gap_p99_ms") == pytest.approx(60.0)
    assert read("ttft_p50_ms") == pytest.approx(120.0)
    assert read("ttft_p95_ms") == pytest.approx(300.0)
    assert read("out_tok_s") == pytest.approx(1.2)
    assert read("device_idle") is None      # nothing to read: left out


@pytest.mark.parametrize("xs,p,want", [
    ([], 50, None), ([3.0], 95, 3.0), (list(range(1, 101)), 50, 51),
    (list(range(1, 101)), 95, 95), ([5, 1, 3], 50, 3)])
def test_percentile(xs, p, want):
    assert metrics.percentile(xs, p) == want


def test_counter_metrics():
    start = {"serve_decode_ticks_total": 100, "decode_fused_ticks_total": 80,
             "decode_fused_steps_total": 320,
             "serve_prefix_tokens_saved_total": 1000}
    end = {"serve_decode_ticks_total": 300, "decode_fused_ticks_total": 260,
           "decode_fused_steps_total": 1040, "serve_kv_total_pages": 1025,
           "serve_prefix_tokens_saved_total": 1176}
    recs = [_rec(5.0, 5.0, [(5.5, 370), (6.0, 370)], 6.1),
            _rec(5.0, 5.0, [(5.5, 0)], 5.6)]
    obs = metrics.Observations(
        recs, 5.0, 10.0, counters_start=start, counters_end=end,
        samples=[(6.0, {"serve_kv_free_pages": 600}),
                 (7.0, {"serve_kv_free_pages": 205}),
                 (20.0, {"serve_kv_free_pages": 0})],
        stretch_start=start, stretch_end=end, stretch_s=4.0)
    assert obs.decode_steps() == 720 + 20       # fused steps + plain ticks
    read = lambda m: manifest.load_reader(
        os.path.join(ROOT, "benchmark"), m)(obs)
    assert read("batch_mean") == pytest.approx(1.0)
    assert read("tick_ms") == pytest.approx(4000.0 / 740)
    assert read("prefix_saved") == pytest.approx(100.0 * 176 / 202)
    assert read("kv_pages_peak") == pytest.approx(80.0)


def test_decode_bw_util_counts_every_chips_memory_system():
    """A model sharded over four chips is read by four memory systems:
    the same steps are a quarter of the peak; on one chip the value is
    the old expression's, bit for bit."""
    from types import SimpleNamespace
    start = {"serve_decode_ticks_total": 0, "decode_fused_ticks_total": 0,
             "decode_fused_steps_total": 0}
    end = {"serve_decode_ticks_total": 500, "decode_fused_ticks_total": 500,
           "decode_fused_steps_total": 2000}
    recs = [_rec(5.0, 5.0, [(5.5, 30000), (6.0, 30000)], 6.1)]
    read = manifest.load_reader(os.path.join(ROOT, "benchmark"),
                                "decode_bw_util")
    cfg = _cfg("mixtral-8x7b-v0.1-l6")
    peaks = roofline.peaks_for("TPU v5 lite")

    def at(chips):
        return read(metrics.Observations(
            recs, 5.0, 51.0, counters_start=start, counters_end=end,
            cell=SimpleNamespace(config=cfg, chips=chips), peaks=peaks))

    was = (100.0 * roofline.decode_step_bytes(cfg, rows=30.0, context=30101.0)
           * 2000 / (51.0 * peaks["hbm_bytes_per_s"]))
    assert at(1) == was and 0 < was < 100
    assert at(4) == pytest.approx(was / 4)


# -- the manifest and its files ----------------------------------------------

def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # 2 + 14 runs a cell, each run_seconds + 60, 180 s a cell to compile,
    # 1200 s spare, at the full 24 cells, inside 43200 s.
    assert ((2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180
            + 1200) <= 43200
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for c in MANIFEST["configs"]:
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_agrees_with_its_files(name):
    cell = manifest.load_cell(name, ROOT)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    assert NAME.match(name) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200 and cell.chips in (1, 4)
    assert cell.config["name"] == cell.config_name
    if cell.traffic["loop"] == "open":
        assert cell.traffic["rate_rps"] > 0, "an open-loop cell fixes a rate"
    else:
        assert cell.traffic["clients"] > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell "
                                   "does not report")
        assert callable(manifest.load_reader(cell.root, m["name"]))
    # Every published number of the configuration is in its file, and
    # only depth may differ from the source.
    entry_c = next(c for c in MANIFEST["configs"]
                   if c["name"] == cell.config_name)
    assert sorted(cell.config["reduced"]) == sorted(entry_c["reduced"])
    assert set(entry_c["reduced"]) <= {"num_hidden_layers"}
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "rope_theta"):
        assert key in cell.config
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"]) == (4096, 14336, 32, 8)
    # The longest prompt and its answer fit the serving context, and
    # the warmed buckets reach the longest prompt.
    p = cell.traffic["prompt"]
    longest = (len(p["head"]) + p["body_tokens"]["max"] + len(p["tail"]) + 1)
    assert longest + cell.traffic["output_tokens"]["max"] \
        <= int(cell.config["stack"]["SERVE_MAX_SEQ"])
    assert max(cell.traffic["warmup_buckets"]) >= longest


# -- the roofline's arithmetic against the issue's ---------------------------

def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_roofline_against_hand_arithmetic():
    m, x = _cfg("mistral-7b-v0.3"), _cfg("mixtral-8x7b-v0.1-l6")
    # ISSUE 22: "about 7.0 GB int8 layers", "4.3 GB int8 KV" (32 x 2048;
    # 4.43 with the float32 scales), "about 8.7 GB int8 layers".
    assert 32 * roofline.layer_weight_bytes(m) == pytest.approx(6.98e9, rel=.01)
    assert 6 * roofline.layer_weight_bytes(x) == pytest.approx(8.71e9, rel=.01)
    assert roofline.kv_pool_bytes(m, 32, 2048) == pytest.approx(4.43e9, rel=.01)
    assert roofline.kv_pool_bytes(x, 32, 2048) == pytest.approx(0.83e9, rel=.01)
    # by hand: one layer = 4096*6144 + 4096*4096 + 3*4096*14336 weights
    hand = 4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336
    assert roofline.layer_weight_bytes(m) == pytest.approx(hand, rel=.001)
    # "9.0 GB a step is 10.9 ms at peak" (Mixtral, 32 rows, context 350)
    step = roofline.decode_step_bytes(x, rows=32, context=350)
    assert step == pytest.approx(9.0e9, rel=.01)
    peaks = roofline.peaks_for("TPU v5 lite")
    secs, side = roofline.least_seconds(
        roofline.decode_step_flops(x, 32, 350), step, peaks)
    assert side == "memory" and secs == pytest.approx(10.9e-3, rel=.02)
    # The dense step: the issue's 7.25 GB left most of the KV out; 32
    # rows at a context of 350 read 0.74 GB of it.
    assert roofline.decode_step_bytes(m, 32, 350) == pytest.approx(
        6.98e9 + 0.134e9 + 32 * 350 * 66560, rel=.01)
    # "prefill of about 270 unshared tokens: 34 ms at 116 TFLOP/s"
    assert roofline.prefill_chunk_flops(m, 270, 135) / 116e12 == \
        pytest.approx(34e-3, rel=.06)
    # experts touched: one row reaches 2, 32 rows reach all 8
    assert roofline.experts_touched(1, 2, 8) == pytest.approx(2.0)
    assert roofline.experts_touched(32, 2, 8) == pytest.approx(8.0, abs=.01)
    assert roofline.layer_weight_bytes(x, rows=1) < \
        0.3 * roofline.layer_weight_bytes(x)


def test_unknown_device_is_an_error():
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("cpu")
    with pytest.raises(KeyError):
        roofline.peaks_for("_source")


# -- the trace reduction ------------------------------------------------------

class _Ev:
    def __init__(self, d):
        self.name, self.start_ns = d["name"], d["start_ns"]
        self.duration_ns, self.stats = d["dur_ns"], list(d["stats"].items())


class _Line:
    def __init__(self, d):
        self.name, self.events = d["line"], [_Ev(e) for e in d["events"]]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes(sample):
    by = {}
    for d in sample:
        by.setdefault(d["plane"], []).append(_Line(d))
    return [_Plane(k, v) for k, v in by.items()]


def test_trace_reduction_by_hand():
    ev = lambda n, s, d, **st: {"name": n, "start_ns": s, "dur_ns": d,
                                "stats": st}
    planes = _planes([
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ev("while.1", 1000, 5000),               # spans its body
            ev("fusion.2", 1000, 2000), ev("custom-call.3", 3500, 2000,
                                           hlo="tpu_custom_call mosaic"),
            ev("fusion.2", 8000, 1000)]},
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            ev("jit__decode", 1000, 5000), ev("jit__decode", 8000, 1000)]},
        {"plane": "/host:CPU", "line": "sched/123", "events": [
            ev("dispatch", 0, 1000), ev("readback", 5900, 2200),
            ev("loop", 0, 10000)]},
    ])
    r = trace_reduce.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(6e-6)        # [1,6) and [8,9)
    ops = dict(r["device_ops"])
    assert ops["fusion.2"] == pytest.approx(3e-6)
    assert ops["custom-call.3"] == pytest.approx(2e-6)
    assert ops["while.1"] == pytest.approx(1e-6)     # 5 less its children
    assert r["pallas_s"] == pytest.approx(2e-6)
    gaps = dict(r["idle_gaps"])
    assert gaps["sched:readback"] == pytest.approx(2e-6)    # [6,8)
    assert gaps["sched:dispatch"] == pytest.approx(1e-6)    # [0,1)
    assert gaps["sched:loop"] == pytest.approx(1e-6)        # [9,10)
    assert r["modules"][0][:1] == ["jit__decode"] and r["modules"][0][2] == 2
    assert trace_reduce.reduce_planes(_planes([
        {"plane": "/host:CPU", "line": "t/1", "events": [ev("x", 0, 5)]}
    ])) == {}


def test_trace_reduction_walks_planes_that_can_be_walked_once():
    """``ProfileData.planes`` is an iterator (jaxlib 0.9.0): handed one,
    the reduction still finds the host's events, and the idle gaps carry
    their names (every ledger row before PR 25 read "(no host event)")."""
    ev = lambda n, s, d: {"name": n, "start_ns": s, "dur_ns": d, "stats": {}}
    planes = _planes([
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ev("fusion.1", 1000, 2000), ev("fusion.1", 7000, 1000)]},
        {"plane": "/host:CPU", "line": "sched/77", "events": [
            ev("sched.admit", 2900, 4200), ev("sched.readback", 0, 1000)]},
    ])
    want = trace_reduce.reduce_planes(planes)
    once = trace_reduce.reduce_planes(iter(planes))
    assert once == want
    gaps = dict(once["idle_gaps"])
    assert gaps["sched:sched.admit"] == pytest.approx(4e-6)     # [3,7)
    assert gaps["sched:sched.readback"] == pytest.approx(1e-6)  # [0,1)
    assert "(no host event)" not in gaps


def test_trace_reduction_on_a_recorded_trace():
    """The first events of every line of a real v5e trace (PR 22, kept by
    run.py --sample): the planes and lines are where the reduction looks
    for them, and its numbers are consistent."""
    path = os.path.join(HERE, "data", "trace_sample_v5e.json")
    with open(path) as f:
        sample = json.load(f)
    planes = _planes(sample)
    assert any(p.name == "/device:TPU:0" for p in planes)
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    assert trace_reduce.OPS_LINE in [ln.name for ln in dev.lines]
    r = trace_reduce.reduce_planes(planes)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["chips"] == 1
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * 1.0001


# -- the hooks into the program ----------------------------------------------

def test_tokenizer_hook_is_where_serve_cell_expects_it(monkeypatch):
    """The random-weights path must take its tokenizer from the module
    global serve.engine.ByteTokenizer, and api.main must look the engine
    builder up when called: serve_cell.py replaces both names."""
    import inspect
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.serve import api, engine
    src = inspect.getsource(engine.build_engine_from_env)
    assert "ByteTokenizer(vocab_size=config.vocab_size)" in src
    assert "from .engine import build_engine_from_env" in \
        inspect.getsource(api.main)
    tok = serve_cell.make_tokenizer_class()(vocab_size=32768)
    ids = [0, 65, 255, 256, 257, 258, 20991, 20992, 32767]
    text = tok.decode(ids)
    assert len(text) == len(ids) and "�" not in text
    assert all(ch.isprintable() and not ch.isspace() for ch in text)
    assert tok.encode("abc", add_bos=True) == [256, 97, 98, 99]
    assert not 0 <= tok.eos_id < 32768          # no id stops a stream
    monkeypatch.setattr(engine, "ByteTokenizer", engine.ByteTokenizer)
    monkeypatch.setattr(engine, "build_engine_from_env",
                        engine.build_engine_from_env)
    from p2p_llm_chat_tpu.models import configs
    monkeypatch.setitem(configs.CONFIGS, "mistral-7b-v0.3", None)
    captured = {}
    serve_cell.install(_cfg("mistral-7b-v0.3"), captured)
    c = configs.CONFIGS["mistral-7b-v0.3"]
    assert (c.hidden_size, c.num_layers, c.num_kv_heads, c.head_dim,
            c.vocab_size, c.rope_theta, c.eos_token_ids) == \
        (4096, 32, 8, 128, 32768, 1e6, ())
    assert engine.ByteTokenizer(512).decode([300]) != ""
    x = serve_cell.model_config(_cfg("mixtral-8x7b-v0.1-l6"))
    assert (x.num_experts, x.num_experts_per_tok, x.num_layers,
            x.moe_capacity_factor, x.is_moe) == (8, 2, 6, 2.0, True)


def test_traced_stretch_is_counted_in_chip_seconds():
    """The trace holds every chip's events and ``stop_trace`` writes them
    all while the window runs on: four chips are traced for a quarter of
    the time, one chip for what it always was."""
    from benchmark import run
    mon = lambda chips: run.Monitor("http://x", "http://y", None, True,
                                    chips)
    assert mon(1).trace_stretch_s == run.TRACE_STRETCH_S == 4.0
    assert mon(4).trace_stretch_s == 1.0


def test_last_line_contract():
    """What run.py prints last holds the contract's keys and no others
    (the whole object is built in one place)."""
    import inspect
    from benchmark import run
    src = inspect.getsource(run.run_cell)
    assert 'last = {"correct": not faults, "attempted": attempted, ' \
        '"failed": failed,' in src
    assert '"metrics": reported,' in src and '"device": {k: device[k]' in src
    assert src.count('last["') == 1 and 'last["breakdown"]' in src


# -- the traffic's steadiness, and the reference's teeth ----------------------

def test_offered_work_hardly_varies_with_the_seed():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "chat-steady.json")) as f:
        tr = dict(json.load(f), rate_rps=2.0)
    assert tr["stratify"] == 32
    counts, work = set(), []
    for seed in range(1, 9):
        counts.add(len(traffic.arrival_times(tr, seed, 45.0)))
        block = [traffic.make_session(tr, seed, i).turns[0]
                 for i in range(64)]
        work.append((sum(len(t.prompt) for t in block),
                     sum(t.num_predict for t in block)))
    assert counts == {90}           # the count is the rate's, not the seed's
    # The mix replays one design: another seed displaces each arrival by
    # at most 50 ms and each length by at most 3%, and changes the bytes.
    assert tr["design_seed"] == 22 and tr["seed_jitter"] == {
        "arrival_s": 0.05, "length": 0.03}
    t1, t2 = (traffic.arrival_times(tr, s, 45.0) for s in (1, 2))
    assert t1 != t2 and max(abs(a - b) for a, b in zip(t1, t2)) <= 0.1001
    for i in range(40):
        a, b = (traffic.make_session(tr, s, i).turns[0] for s in (1, 2))
        assert abs(len(a.prompt) - len(b.prompt)) <= 0.0601 * 1500 * 1.01
        assert abs(len(a.prompt) - 96 - (len(b.prompt) - 96)) \
            <= 0.061 * (len(a.prompt) - 96) + 1
        assert a.prompt[88:120] != b.prompt[88:120]
    free = {k: v for k, v in tr.items() if k not in ("design_seed",
                                                     "seed_jitter")}
    assert traffic.arrival_times(free, 1, 45.0)[:3] != \
        traffic.arrival_times(free, 2, 45.0)[:3]
    for k in (0, 1):                # whole blocks: within 7% of each other
        xs = [w[k] for w in work]   # (independent draws: about 25%)
        assert max(xs) / min(xs) < 1.07, xs
    # ... and it is still the lognormal it says it is.
    bodies = sorted(len(traffic.make_session(tr, 5, i).turns[0].prompt) - 96
                    for i in range(640))
    assert 185 <= bodies[320] <= 215 and bodies[0] >= 32 \
        and bodies[-1] <= 1500


@pytest.mark.parametrize("experts", [0, 4])
def test_reference_has_teeth(experts):
    """A skipped term fails the comparison; float32 noise does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference
    cfg = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 3, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 300,
           "rope_theta": 1e4, "rms_norm_eps": 1e-5}
    if experts:
        cfg.update(num_local_experts=experts, num_experts_per_tok=2)
    rng = np.random.default_rng(0)
    nrm = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),
                                 jnp.float32)
    H, E, V = 64, 128, 300
    layers = [{"attn_norm": jnp.ones(H), "mlp_norm": jnp.ones(H),
               "wq": nrm(H, 64), "wk": nrm(H, 32), "wv": nrm(H, 32),
               "wo": nrm(64, H), "w_gate": nrm(H, E), "w_up": nrm(H, E),
               "w_down": nrm(E, H), "router": nrm(H, max(experts, 1))}
              for _ in range(3)]
    exp = {(l, e): (nrm(H, E), nrm(H, E), nrm(E, H))
           for l in range(3) for e in range(experts)}
    embed, head = jnp.asarray(rng.standard_normal((V, H)), jnp.float32), \
        nrm(H, V)
    tokens = jnp.asarray(rng.integers(0, V, (2, 24)), jnp.int32)
    run_ = lambda c, lw: reference.forward(
        c, tokens, embed, lw, jnp.ones(H), head,
        expert_weights=(lambda l, e: exp[(l, e)]) if experts else None)[0]
    good = run_(cfg, lambda l: layers[l])
    assert good.shape == (2, 24, V)
    noisy = good * (1 + 1e-3 * jnp.asarray(rng.standard_normal(good.shape),
                                           jnp.float32))
    assert reference.compare(noisy, good, routed=bool(experts))["ok"]
    no_rope = run_(dict(cfg, rope_theta=1.0 + 1e-9), lambda l: layers[l])
    assert not reference.compare(no_rope, good, routed=bool(experts))["ok"]
    no_norm = run_(cfg, lambda l: dict(layers[l],
                                       mlp_norm=0.5 * jnp.ones(H)))
    assert not reference.compare(no_norm, good, routed=bool(experts))["ok"]
    # Causal: a position's logits do not depend on later tokens.
    later = tokens.at[:, 12:].set(7)
    cut = reference.forward(cfg, later, embed, lambda l: layers[l],
                            jnp.ones(H), head, expert_weights=(
                                lambda l, e: exp[(l, e)]) if experts
                            else None)[0]
    np.testing.assert_allclose(np.asarray(cut[:, :12]),
                               np.asarray(good[:, :12]), rtol=1e-4,
                               atol=1e-4)
    if experts:
        w, margin = jax.jit(lambda x, r: reference.route(x, r, 2))(
            jnp.asarray(rng.standard_normal((50, H)), jnp.float32),
            layers[0]["router"])
        assert bool(jnp.all(jnp.sum(w > 0, -1) == 2))
        np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0,
                                   rtol=1e-5)
        assert reference.expert_overflow(w, 1000) == 0
        assert reference.expert_overflow(w, 10) == int(
            jnp.sum(jnp.maximum(jnp.sum(w > 0, 0) - 10, 0)))
