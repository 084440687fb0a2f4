"""The program's spans in the profiler's trace (ISSUE 25).

``telemetry.trace.span`` opens a ``jax.profiler.TraceAnnotation`` named
``hetu:<span>`` beside whatever the JSONL tracer does, so a profiler session
is the only switch: while one runs the spans land in the xplane, nested as
the calls nest; while none runs the program computes the same tokens and
loss as with the spans taken out, and lowers to the same programs.

One session is recorded per module (a tiny ``PagedServeEngine`` behind the
scheduler, then three ``Executor.run('train')`` steps) and read back with
``jax.profiler.ProfileData``; every test that starts a profiler runs under a
time limit of its own.
"""

import signal
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from hetu_tpu import optim
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.telemetry import trace
from hetu_tpu.train.executor import Executor

pytestmark = pytest.mark.telemetry

PROFILER_LIMIT_S = 120
PREFIX = trace.PROFILER_PREFIX
SEAMS = ("prep", "launch", "fetch", "post")
PROMPT_LENS = (5, 20, 33)       # one, two and three chunks of 16
TRAIN_STEPS = 3


@contextmanager
def time_limit(seconds: int):
    """SIGALRM after ``seconds``: a profiler that hangs fails its own test
    and not the run's limit."""
    def on_alarm(signum, frame):
        raise TimeoutError(f"profiler test over its {seconds} s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@contextmanager
def profiled(log_dir):
    """A profiler session writing under ``log_dir``, under the limit."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the spans, not every Python call
    with time_limit(PROFILER_LIMIT_S), \
            jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


def hetu_threads(log_dir) -> list:
    """Per host thread that opened any: its ``hetu:`` events as (name, start,
    end, ids), parents before their children."""
    from jax.profiler import ProfileData

    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    threads = []
    with time_limit(PROFILER_LIMIT_S):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                evs = [(e.name.split("#")[0][len(PREFIX):],
                        float(e.start_ns),
                        float(e.start_ns) + float(e.duration_ns),
                        dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIX)]
                if evs:
                    threads.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return threads


def _model():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


def _serving(model, variables):
    # no prefix sharing: the same three prompts run again and again, and
    # each time every chunk has to run as it did the first time
    eng = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                           page_size=8, prefill_chunk=16,
                           prefix_sharing=False)
    return eng, ContinuousBatchingScheduler(eng)


def _serve(sched) -> list:
    """The same three requests every time; their tokens."""
    g = np.random.default_rng(7)
    reqs = [Request(prompt=[int(t) for t in g.integers(0, 97, n)],
                    max_tokens=6) for n in PROMPT_LENS]
    for r in reqs:
        sched.submit(r)
    for _ in range(200):
        if not sched.has_work():
            break
        sched.step()
    assert all(r.status == "ok" for r in reqs)
    return [list(r.tokens) for r in reqs]


def _training(model):
    ex = Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3), seed=0)
    g = np.random.default_rng(3)
    return ex, (g.integers(0, 97, (2, 32)).astype(np.int32),)


def _train(ex, variables, batch):
    """Three steps from the same fresh state; (losses, state after)."""
    state = ex.init_state(variables)
    losses = []
    for _ in range(TRAIN_STEPS):
        state, metrics = ex.run("train", state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state


def _lowered(eng, ex, state, batch) -> dict:
    """Text of the decode, chunk and train programs as they lower now."""
    aux_d = jax.ShapeDtypeStruct((2, 4 + 4), np.int32)
    n_table = eng.cache.pages_per_slot
    aux_c = jax.ShapeDtypeStruct((3 * 16 + n_table + 2,), np.int32)
    args = (eng.params, eng.cache.k, eng.cache.v)
    return {
        "decode": eng._build_decode().lower(*args, aux_d).as_text(),
        "chunk": eng._build_chunk(n_table).lower(*args, aux_c).as_text(),
        "train": ex.lower("train", state, batch).as_text(),
    }


class _Off:
    """``trace.span`` and ``trace.instant`` taken out: the uninstrumented
    reference."""

    def __enter__(self):
        self.kept = trace.span, trace.instant
        trace.span = lambda *a, **k: trace.NULL_SPAN
        trace.instant = lambda *a, **k: None

    def __exit__(self, *exc):
        trace.span, trace.instant = self.kept


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One profiler session over the serving steps and the train steps,
    with what the same code gave before it, and without its spans."""
    model, variables = _model()
    out = {}
    with _Off():
        eng, sched = _serving(model, variables)
        ex, batch = _training(model)
        out["tokens_bare"] = _serve(sched)
        out["loss_bare"], state = _train(ex, variables, batch)
        out["programs_bare"] = eng.compiled_executables()
        out["lowered_bare"] = _lowered(eng, ex, state, batch)

    eng, sched = _serving(model, variables)
    ex, batch = _training(model)
    out["tokens_no_session"] = _serve(sched)     # also the warm-up
    out["loss_no_session"], state = _train(ex, variables, batch)
    out["programs_no_session"] = eng.compiled_executables()
    out["lowered_no_session"] = _lowered(eng, ex, state, batch)

    log = tmp_path_factory.mktemp("xplane")
    with profiled(log):
        out["tokens_session"] = _serve(sched)
        out["loss_session"], state = _train(ex, variables, batch)
        out["lowered_session"] = _lowered(eng, ex, state, batch)
    out["programs_session"] = eng.compiled_executables()
    out["rounds"] = eng.metrics.rounds()    # the round log, both passes
    threads = hetu_threads(log)
    assert len(threads) == 1, "one thread did the work"
    out["events"] = threads[0]
    return out


def _named(events, name):
    return [e for e in events if e[0] == name]


def _children(events, parent):
    """Events strictly inside ``parent`` and not ``parent`` itself, in
    order of start."""
    return [e for e in events
            if e is not parent and e[1] >= parent[1] and e[2] <= parent[2]]


# ------------------------------------------------------- the spans exist

@pytest.mark.parametrize("name", [
    "serve.step", "serve.admit", "serve.advance_prefills", "serve.evict",
    "serve.decode", *(f"serve.decode.{s}" for s in SEAMS),
    "serve.prefill_chunk", *(f"serve.prefill_chunk.{s}" for s in SEAMS),
    "train.host_to_device", "train.step.train",
])
def test_span_is_in_the_xplane(recorded, name):
    assert _named(recorded["events"], name), name


def test_a_compile_inside_a_session_is_a_zero_length_annotation(tmp_path):
    """``serve.recompile`` and ``train.compile`` are instants: with a
    session running they are written too, with their ids."""
    model, variables = _model()
    eng, sched = _serving(model, variables)
    ex, batch = _training(model)
    with profiled(tmp_path):
        _serve(sched)
        _train(ex, variables, batch)
    found = {}
    for name, a, b, ids in hetu_threads(tmp_path)[0]:
        found.setdefault(name, []).append((b - a, ids))
    assert found["train.compile"][0][1] == {"subexecutor": "train"}
    kinds = {st["kind"] for _, st in found["serve.recompile"]}
    assert kinds == {"prefill_chunk", "decode"}
    longest_span = max(d for d, _ in found["serve.step"])
    assert all(d < longest_span / 10 for d, _ in found["serve.recompile"])


# ------------------------------------------------------------- nesting

@pytest.mark.parametrize("parent", ["serve.decode", "serve.prefill_chunk"])
def test_four_seams_tile_their_parent(recorded, parent):
    """prep, launch, fetch, post: inside the parent, in that order, not
    overlapping, and together within 5% of it."""
    events = recorded["events"]
    parents = _named(events, parent)
    assert len(parents) >= 5
    shares = []
    for p in parents:
        kids = _children(events, p)
        assert [k[0] for k in kids if k[0].count(".") == 2] == \
            [f"{parent}.{s}" for s in SEAMS]
        seams = [k for k in kids if k[0].startswith(parent + ".")]
        for a, b in zip(seams, seams[1:]):
            assert a[2] <= b[1]
        shares.append(sum(k[2] - k[1] for k in seams) / (p[2] - p[1]))
    # the median: a thread descheduled between two seams on a busy test
    # machine is not the program's gap
    assert max(shares) <= 1.0 and np.median(shares) >= 0.95


def test_scheduler_step_holds_its_phases_in_order(recorded):
    events = recorded["events"]
    steps = _named(events, "serve.step")
    assert len(steps) >= 6
    engine_calls = 0
    for st in steps:
        kids = _children(events, st)
        top = [k[0] for k in kids
               if not any(o is not k and o[1] <= k[1] and k[2] <= o[2]
                          for o in kids)]
        assert top[:2] == ["serve.admit", "serve.advance_prefills"]
        assert set(top[2:]) <= {"serve.decode", "serve.evict"}
        if "serve.decode" in top:
            assert top[2:] == ["serve.decode", "serve.evict"]
        engine_calls += sum(k[0] in ("serve.decode", "serve.prefill_chunk")
                            for k in kids)
        # a chunk runs inside advance_prefills
        adv = next(k for k in kids if k[0] == "serve.advance_prefills")
        for c in (k for k in kids if k[0] == "serve.prefill_chunk"):
            assert adv[1] <= c[1] and c[2] <= adv[2]
    every = _named(events, "serve.decode") + \
        _named(events, "serve.prefill_chunk")
    assert engine_calls == len(every)   # none outside a step


def test_train_spans_follow_each_other(recorded):
    events = recorded["events"]
    h2d = _named(events, "train.host_to_device")
    step = _named(events, "train.step.train")
    assert len(h2d) == len(step) == TRAIN_STEPS
    for a, b in zip(h2d, step):
        assert a[2] <= b[1]


# ----------------------------------------------------------------- ids

@pytest.mark.parametrize("name,keys", [
    ("serve.step", {"step"}),
    ("serve.decode", {"active"}),
    ("serve.decode.launch", {"pages", "batch", "seq"}),
    ("serve.decode.fetch", {"seq"}),
    ("serve.prefill_chunk", {"slot"}),
    ("serve.prefill_chunk.launch", {"start", "tokens", "bucket",
                                    "view_bytes", "seq"}),
    ("serve.prefill_chunk.fetch", {"seq"}),
    ("train.step.train", {"step"}),
])
def test_ids_decode_from_the_event(recorded, name, keys):
    evs = _named(recorded["events"], name)
    assert evs
    for e in evs:
        assert set(e[3]) == keys
        assert all(isinstance(v, int) for v in e[3].values())


def test_ids_say_what_ran(recorded):
    events = recorded["events"]
    ordinals = [e[3]["step"] for e in _named(events, "serve.step")]
    assert ordinals == list(range(ordinals[0], ordinals[0] + len(ordinals)))
    chunks = _named(events, "serve.prefill_chunk.launch")
    assert sorted(c[3]["tokens"] for c in chunks) == \
        sorted([5, 16, 4, 16, 16, 1])    # 5, 20 and 33 in chunks of 16
    assert all(c[3]["bucket"] == 16 for c in chunks)
    assert max(e[3]["active"] for e in _named(events, "serve.decode")) == 2


# ------------------------------------------- a launch's identity (ISSUE 36)

def test_seq_rises_by_one_a_launch_across_rounds_and_chunks(recorded):
    """One counter an engine: a decode round and a prefill chunk draw from
    the same numbers, in the order they were launched."""
    events = recorded["events"]
    launches = sorted(_named(events, "serve.decode.launch")
                      + _named(events, "serve.prefill_chunk.launch"),
                      key=lambda e: e[1])
    seqs = [e[3]["seq"] for e in launches]
    assert len(seqs) >= 12 and {e[0] for e in launches} == {
        "serve.decode.launch", "serve.prefill_chunk.launch"}
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    # the session was the engine's second pass over the same requests
    assert seqs[0] == len(seqs) + 1


@pytest.mark.parametrize("kind", ["serve.decode", "serve.prefill_chunk"])
def test_a_fetch_carries_the_seq_of_the_launch_it_waits_for(recorded, kind):
    events = recorded["events"]
    calls = _named(events, kind)
    assert calls
    for call in calls:
        kids = {k[0]: k for k in _children(events, call)}
        launch, fetch = kids[kind + ".launch"], kids[kind + ".fetch"]
        assert launch[3]["seq"] == fetch[3]["seq"]
        assert launch[2] <= fetch[1]


# ------------------------------------------------ the round log (ISSUE 54)

def test_the_round_log_has_a_row_a_launch_numbered_as_the_spans_are(recorded):
    """One row an engine call, rounds and chunks together, ``seq`` rising by
    one from the engine's first call; the session's rows are the rows whose
    ``seq`` its launch spans carry, and each is of its span's kind."""
    from hetu_tpu.serve.metrics import CHUNK, DECODE, ROUND_FIELDS

    rows = recorded["rounds"]
    assert rows.dtype == np.int64 and rows.shape[1] == len(ROUND_FIELDS)
    assert rows[:, 0].tolist() == list(range(1, len(rows) + 1))
    assert set(rows[:, 1].tolist()) == {DECODE, CHUNK}
    kind_of = {"serve.decode.launch": DECODE,
               "serve.prefill_chunk.launch": CHUNK}
    launches = {e[3]["seq"]: kind_of[e[0]] for e in recorded["events"]
                if e[0] in kind_of}
    assert len(launches) == len(rows) // 2    # the second pass of two
    assert {int(r[0]): int(r[1]) for r in rows[len(rows) // 2:]} == launches


def test_a_rows_phases_tile_its_call_and_calls_follow_each_other(recorded):
    """``t_prep <= t_launch <= t_fetch <= t_post <= t_close``: the four
    phases are the differences, so they tile the call with no gap; and a
    call opens after the call before it closed."""
    rows = recorded["rounds"]
    seams = rows[:, 2:7]
    assert (np.diff(seams, axis=1) >= 0).all()
    assert (seams[1:, 0] >= seams[:-1, 4]).all()
    assert (seams[:, 4] - seams[:, 0]).min() > 0


def test_a_rows_launch_and_fetch_lie_inside_the_spans_of_its_seq(recorded):
    """The log reads ``time.monotonic_ns()`` as the first statement of each
    seam's span, the profiler stamps the span on a clock of its own: there
    is ONE offset between the two under which every row's ``t_launch`` lies
    inside the launch span of its ``seq`` and its ``t_fetch`` inside the
    fetch span, and the spans' openings sit within microseconds of the
    log's reads under it."""
    events = recorded["events"]
    spans = {}
    for name, a, b, ids in events:
        seam = name.rsplit(".", 1)[-1]
        if seam in ("launch", "fetch"):
            spans[(ids["seq"], seam)] = (a, b)
    rows = [r for r in recorded["rounds"] if (int(r[0]), "launch") in spans]
    assert len(rows) >= 12
    lo, hi, opened = -np.inf, np.inf, []
    for seq, _, _, t_launch, t_fetch, *_ in rows:
        for seam, t in (("launch", t_launch), ("fetch", t_fetch)):
            a, b = spans[(int(seq), seam)]
            lo, hi = max(lo, a - t), min(hi, b - t)
            opened.append(a - t)
    assert lo <= hi, "no one offset puts every read inside its span"
    # a span's opening to the read inside it: the annotation's own cost
    assert np.median(np.asarray(opened) - lo) > -50_000


def test_train_step_span_carries_the_steps_number(recorded):
    """``step`` counts the train steps the executor has issued, this one
    included; the session's three follow the three before it."""
    steps = [e[3]["step"]
             for e in _named(recorded["events"], "train.step.train")]
    assert steps == [TRAIN_STEPS + 1 + i for i in range(TRAIN_STEPS)]


@pytest.mark.parametrize("program,module", [
    ("decode", "jit_hetu_serve_decode"),
    ("chunk", "jit_hetu_serve_prefill_chunk"),
    ("train", "jit__train_step"),
])
def test_program_lowers_under_its_own_module_name(recorded, program, module):
    """The jitted function's name is the program's on the device (the
    profiler's ``XLA Modules`` line, compile logs, HLO dumps)."""
    text = recorded["lowered_session"][program]
    assert f"module @{module} " in text
    assert "module @jit_fn" not in text


@pytest.mark.parametrize("chunks,spans", [(0, 9), (1, 14)])
def test_spans_a_scheduler_step(recorded, chunks, spans):
    """A step with a decode round opens 9 spans, 14 with one chunk beside
    it: the launch ids added none."""
    events = recorded["events"]
    counted = []
    for st in _named(events, "serve.step"):
        kids = _children(events, st)
        names = [k[0] for k in kids]
        if names.count("serve.decode") == 1 \
                and names.count("serve.prefill_chunk") == chunks:
            counted.append(1 + len(kids))
    assert counted and set(counted) == {spans}


def test_params_held_says_what_the_build_did_to_the_weights(tmp_path):
    """``serve.params_held`` is one instant at build, beside
    ``serve.cache_spec``: the leaves given, those held in another dtype,
    those held in another shape or structure (``relaid``, ISSUE 45: GPT-2's
    fused projection, transposed) and the bytes on each side (float32
    compute here: nothing re-typed)."""
    model, variables = _model()
    with profiled(tmp_path):
        eng, _ = _serving(model, variables)
    events = hetu_threads(tmp_path)[0]
    (held,) = _named(events, "serve.params_held")
    leaves = jax.tree_util.tree_leaves(eng.params)
    nbytes = sum(a.nbytes for a in leaves)
    assert held[3] == {"leaves": len(leaves), "retyped": 0, "relaid": 1,
                       "bytes_given": nbytes, "bytes_held": nbytes}
    assert [k for k in eng.params["blocks"]["attn"] if k.endswith("_t")] \
        == ["qkv_weight_t"]
    names = [e[0] for e in events]
    assert names.index("serve.params_held") \
        == names.index("serve.cache_spec") + 1


def test_cache_ids_say_what_a_call_holds(tmp_path):
    """``serve.cache_spec`` carries the pools' bytes, and a chunk's launch
    the bytes ONE cache layer's gathered view holds in that call (K and V):
    far under the pools, and under what a view of every layer would be.  A
    decode round gathers no view on a TPU and states none."""
    model, variables = _model()
    with profiled(tmp_path):
        eng, sched = _serving(model, variables)
        _serve(sched)
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    pools = eng.cache.k.nbytes + eng.cache.v.nbytes
    assert spec[3]["pool_bytes"] == pools == 2 * 2 * 33 * 8 * 64 * 4
    row = spec[3]["bytes_per_token"] // spec[3]["cache_layers"]  # K + V
    rounds = _named(events, "serve.decode.launch")
    assert rounds and all(set(e[3]) == {"pages", "batch", "seq"}
                          for e in rounds)
    chunks = _named(events, "serve.prefill_chunk.launch")
    assert {c[3]["view_bytes"] for c in chunks} == {8 * 8 * row}
    assert all(e[3]["view_bytes"] * spec[3]["cache_layers"] < pools
               for e in events if "view_bytes" in e[3])


# ------------------------------------------- the device side is untouched

@pytest.mark.parametrize("what", ["tokens", "loss", "programs"])
@pytest.mark.parametrize("state", ["no_session", "session"])
def test_same_results_as_the_uninstrumented_run(recorded, what, state):
    assert recorded[f"{what}_{state}"] == recorded[f"{what}_bare"]


@pytest.mark.parametrize("program", ["decode", "chunk", "train"])
def test_lowered_program_is_the_same_text(recorded, program):
    bare = recorded["lowered_bare"][program]
    assert "hetu:" not in bare and len(bare) > 1000
    assert recorded["lowered_no_session"][program] == bare
    assert recorded["lowered_session"][program] == bare


# ------------------------------------------------------- the two sinks

def test_span_without_a_tracer_is_an_inert_annotation():
    """With jax loaded and no tracer installed a span is the profiler's
    annotation and nothing else; ``set`` is swallowed."""
    assert not trace.enabled()
    sp = trace.span("anything", {"step": 1})
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as s:
        assert s.set("k", "v") is s
    assert trace.instant("nothing", {"kind": "x"}) is None


def test_both_sinks_at_once(tmp_path):
    """The JSONL tracer keeps its record (late ``set`` included) while a
    session writes the same span into the xplane."""
    t = trace.enable()
    try:
        with profiled(tmp_path):
            with trace.span("both.sinks", {"step": 4}) as sp:
                sp.set("late", 1)
    finally:
        trace.disable()
    ev = next(e for e in t.events if e["name"] == "both.sinks")
    assert ev["args"] == {"step": 4, "late": 1}
    assert [(name, ids) for name, _, _, ids in hetu_threads(tmp_path)[0]] \
        == [("both.sinks", {"step": 4})]


def test_a_process_without_jax_opens_no_annotation(monkeypatch):
    """The lookup reads ``sys.modules``; without jax there the off path is
    the singleton it was."""
    import sys

    monkeypatch.setattr(trace, "_annotation", False)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert trace.span("x") is trace.NULL_SPAN
    assert trace._annotation is None      # looked up once
    monkeypatch.setitem(sys.modules, "jax", jax)
    assert trace.span("y") is trace.NULL_SPAN


# ------------------------------------------ a cache of two groups (ISSUE 32)

def test_a_grouped_caches_ids_reach_the_post_spans(tmp_path):
    """A model whose cache has a window group beside the full one: the
    ``post`` span of every decode round and prefill chunk carries what the
    tables hold (``kv_pages_full``, ``kv_pages_window``), what one group
    would hold (``kv_pages_if_one_group``) and the pages dropped from
    behind the window since the last call, beside the expert layer's
    counts; ``serve.cache_spec`` lists the second group; a chunk's launch
    says what a layer of each group gathers, a decode round's states no
    view."""
    import jax.numpy as jnp

    from hetu_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel

    model = ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=48, expert_ffn_size=16,
        n_routed_experts=8, moe_topk=2, held=(0, 4), window=8,
        max_position=128, dtype=jnp.float32, param_dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=128,
                               page_size=4, prefill_chunk=8, min_bucket=4,
                               prefix_sharing=False)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    full, win = eng.cache.groups
    row = 2 * 2 * 8 * 4                       # K + V of one token, a layer
    assert spec[3]["cache_layers"] == 1 and spec[3]["g1_cache_layers"] == 4
    assert spec[3]["g1_window"] == 8
    assert spec[3]["pool_bytes"] == full.k.nbytes + full.v.nbytes
    assert spec[3]["g1_pool_bytes"] == win.k.nbytes + win.v.nbytes \
        == 4 * (1 + 4 * 6) * 4 * row
    posts = _named(events, "serve.decode.post") \
        + _named(events, "serve.prefill_chunk.post")
    keys = {"moe_held", "moe_zero", "moe_absent", "moe_hit", "moe_grouped",
            "kv_pages_full",
            "kv_pages_window", "kv_pages_if_one_group", "kv_window_released"}
    assert posts and all(set(e[3]) == keys for e in posts)
    assert sum(e[3]["kv_window_released"] for e in posts) \
        == eng.metrics.count("kv_window_released") == win.released > 0
    for e in posts:
        ids = e[3]
        assert ids["kv_pages_full"] * 5 == ids["kv_pages_if_one_group"]
        assert ids["kv_pages_window"] <= 4 * 5 * 4   # 4 layers, ring, slots
        # every held pair a grouped call's (ISSUE 55): an id on the span
        # that was there, and no span more a scheduler step
        assert ids["moe_grouped"] == ids["moe_held"]
    assert eng.metrics.count("moe_grouped") == sum(
        e[3]["moe_grouped"] for e in posts) > 0
    for step in _named(events, "serve.step"):
        assert {e[0] for e in _children(events, step)} <= {
            "serve.admit", "serve.advance_prefills", "serve.evict",
            "serve.decode", "serve.prefill_chunk", "paged_attn.plan",
            "chunk_attn.plan", "serve.recompile",
            *(f"serve.decode.{s}" for s in SEAMS),
            *(f"serve.prefill_chunk.{s}" for s in SEAMS)}
    # at 33 + 6 tokens the grouped cache holds well under one group's pages
    last = max(posts, key=lambda e: e[1])[3]
    assert last["kv_pages_full"] + last["kv_pages_window"] \
        < 0.8 * last["kv_pages_if_one_group"]
    rounds = _named(events, "serve.decode.launch")
    assert rounds and all(set(e[3]) == {"pages", "batch", "seq"}
                          for e in rounds)
    chunks = _named(events, "serve.prefill_chunk.launch")
    assert chunks
    for e in chunks:
        assert e[3]["view_bytes"] == 128 // 4 * 4 * row   # the slot's table
        assert e[3]["g1_view_bytes"] == 5 * 4 * row


@pytest.mark.parametrize("path", ["grouped", "cut"])
def test_a_served_expert_walk_says_which_path_computed_its_pairs(
        tmp_path, monkeypatch, path):
    """``moe_grouped`` on the ``post`` span of every decode round and prefill
    chunk of an LFM2 engine, beside the other ``moe_*`` ids: the call's held
    pairs on either path of the rule (``ops.moe_ops.held_expert_path``),
    since a served walk is evaluated and both paths evaluate by grouped
    calls: experts kept whole, or cut along F (ISSUE 55; the loop that
    counted 0 here is reverse mode's alone)."""
    import jax.numpy as jnp

    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
    from hetu_tpu.ops import moe_ops

    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                        32 * 16 if path == "grouped" else 32 * 16 - 1)
    model = Lfm2MoeModel(Lfm2MoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, expert_ffn_size=16,
        first_dense=1, n_routed_experts=8, moe_topk=2,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention"),
        max_position=64, dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.2, router_init_std=0.5, expert_block_rows=4))
    variables = jax.jit(model.init)(jax.random.PRNGKey(3))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    rounds = _named(events, "serve.decode.post")
    chunks = _named(events, "serve.prefill_chunk.post")
    assert rounds and chunks
    for e in rounds + chunks:
        ids = e[3]
        assert {"moe_held", "moe_hit", "moe_experts", "moe_grouped"} \
            <= set(ids)
        # four expert layers of two choices a row, every expert held
        assert ids["moe_held"] > 0 and ids["moe_held"] % (4 * 2) == 0
        assert ids["moe_grouped"] == ids["moe_held"]
    assert moe_ops.held_expert_path(4, 2, 8, 32, 16) == path
    assert eng.metrics.count("moe_grouped") == sum(
        e[3]["moe_grouped"] for e in rounds + chunks)


# ------------------------------- a state layer of several parts (ISSUE 47)

def test_a_two_part_states_ids_and_the_mixers_plan_and_scopes(tmp_path):
    """A model whose every layer keeps a convolution's rows AND a
    recurrence's matrix: ``serve.cache_spec`` and the ``post`` span of every
    decode round and prefill chunk state the state by part beside the pages;
    ONE ``ssm.plan`` instant a program traced says which form it holds (a
    chunk scans, a round steps); the programs carry the mixer's scopes; a
    scheduler step holds no span more than a model without state."""
    import jax.numpy as jnp

    from hetu_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model

    model = FalconH1Model(FalconH1Config(
        vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_groups=2, ssm_chunk=8, max_position=128,
        dtype=jnp.float32, param_dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=128,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    conv, ssm = (sum(a.nbytes for a in part) for part in eng.cache.state)
    per = eng.cache.spec.part_bytes_per_slot
    assert spec[3]["state_layers"] == 3
    assert spec[3]["bytes_per_slot"] == per["conv"] + per["ssm"]
    assert spec[3]["state_conv_bytes"] == conv == 5 * per["conv"]
    assert spec[3]["state_ssm_bytes"] == ssm == 5 * per["ssm"]
    assert spec[3]["state_bytes"] == conv + ssm
    posts = _named(events, "serve.decode.post") \
        + _named(events, "serve.prefill_chunk.post")
    keys = {"state_slots_held", "state_bytes", "state_conv_bytes",
            "state_ssm_bytes", "kv_bytes_held", "kv_pages_full"}
    assert posts and all(set(e[3]) == keys for e in posts)
    for e in posts:
        ids = e[3]
        assert ids["state_bytes"] == ids["state_conv_bytes"] \
            + ids["state_ssm_bytes"] == ids["state_slots_held"] \
            * (per["conv"] + per["ssm"])
    plans = [e[3] for e in _named(events, "ssm.plan")]
    assert len(plans) == eng.compiled_executables()   # one a program traced
    assert {p["form"] for p in plans} == {"scan", "step"}
    for p in plans:
        assert (p["chunk"], p["heads"], p["d_head"], p["d_state"],
                p["groups"]) == (8, 4, 8, 16, 2)
        assert p["state_bytes_per_slot"] * 3 == per["conv"] + per["ssm"]
        assert p["rows"] == 1 if p["form"] == "step" else p["batch"] == 1
    from paged_programs import traced
    for name, scopes in (
            ("decode", ("hetu.ssm.proj", "hetu.ssm.conv", "hetu.ssm.step",
                        "hetu.ssm.norm", "hetu.attn.full", "hetu.ffn.dense")),
            ("chunk", ("hetu.ssm.proj", "hetu.ssm.conv", "hetu.ssm.scan",
                       "hetu.ssm.norm", "hetu.attn.full", "hetu.ffn.dense"))):
        text = traced(eng, name, batch=4, chunk=8).lower().as_text(
            debug_info=True)
        assert all(scope in text for scope in scopes), name
        assert ("hetu.ssm.scan" in text) == (name == "chunk")
    # the same spans a scheduler step as a model with no state has
    step = _named(events, "serve.step")[0]
    assert {e[0] for e in _children(events, step)} <= {
        "serve.admit", "serve.advance_prefills", "serve.evict",
        "serve.decode", "serve.prefill_chunk", "ssm.plan", "paged_attn.plan",
        "chunk_attn.plan",
        "serve.state_reset", "serve.recompile",
        *(f"serve.decode.{s}" for s in SEAMS),
        *(f"serve.prefill_chunk.{s}" for s in SEAMS)}


def test_a_delta_rule_models_plan_scopes_and_state_gauges_by_part(tmp_path):
    """Gated DeltaNet state layers of two parts beside FEWER cache layers
    than layers (ISSUE 51): ``serve.cache_spec``, both ``post`` spans and the
    gauges state the state by part (``state_conv_bytes``,
    ``state_delta_bytes``) beside the expert layers' counts; ONE ``gdn.plan``
    instant a program traced says which form it holds (a chunk solves in
    chunks, a round steps) and how the triangular system is solved; the
    programs carry the mixer's scopes."""
    import jax.numpy as jnp

    from hetu_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel

    model = Qwen3NextModel(Qwen3NextConfig(
        vocab_size=97, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=8, gdn_value_dim=8, gdn_chunk=8, expert_ffn_size=16,
        shared_ffn_size=16, n_routed_experts=16, moe_topk=4, held=(4, 4),
        max_position=128, dtype=jnp.float32, param_dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=128,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    conv, delta = (sum(a.nbytes for a in part) for part in eng.cache.state)
    per = eng.cache.spec.part_bytes_per_slot
    assert (spec[3]["state_layers"], spec[3]["cache_layers"]) == (3, 1)
    assert spec[3]["bytes_per_slot"] == per["conv"] + per["delta"]
    assert spec[3]["state_conv_bytes"] == conv == 5 * per["conv"]
    assert spec[3]["state_delta_bytes"] == delta == 5 * per["delta"]
    posts = _named(events, "serve.decode.post") \
        + _named(events, "serve.prefill_chunk.post")
    keys = {"state_slots_held", "state_bytes", "state_conv_bytes",
            "state_delta_bytes", "kv_bytes_held", "kv_pages_full",
            *model.step_stats}
    assert posts and all(set(e[3]) == keys for e in posts)
    for e in posts:
        ids = e[3]
        assert ids["state_bytes"] == ids["state_conv_bytes"] \
            + ids["state_delta_bytes"] == ids["state_slots_held"] \
            * (per["conv"] + per["delta"])
        assert ids["moe_experts"] == 4 * 4          # held x expert layers
        assert ids["moe_grouped"] == ids["moe_held"]
    snap = eng.metrics.snapshot()
    assert snap["state_conv_bytes"] > 0 and snap["state_delta_bytes"] > 0
    plans = [e[3] for e in _named(events, "gdn.plan")]
    assert len(plans) == eng.compiled_executables()   # one a program traced
    assert {p["form"] for p in plans} == {"chunk", "step"}
    for p in plans:
        assert (p["chunk"], p["heads_k"], p["heads_v"], p["d_k"], p["d_v"],
                p["solve"]) == (8, 2, 4, 8, 8, "product")
        assert p["state_bytes_per_slot"] * 3 == per["conv"] + per["delta"]
        assert p["rows"] == 1 if p["form"] == "step" else p["batch"] == 1
    from paged_programs import traced
    shared = ("hetu.gdn.proj", "hetu.gdn.conv", "hetu.gdn.norm",
              "hetu.attn.full", "hetu.moe.route", "hetu.moe.experts",
              "hetu.moe.shared")
    for name, own, other in (("decode", "hetu.gdn.step", "hetu.gdn.rule"),
                             ("chunk", "hetu.gdn.rule", "hetu.gdn.step")):
        text = traced(eng, name, batch=4, chunk=8).lower().as_text(
            debug_info=True)
        assert all(scope in text for scope in shared + (own,)), name
        assert other not in text, name


def test_a_sparse_models_counts_plans_and_scopes(tmp_path):
    """Block-sparse layers beside Lightning state layers (ISSUE 56): both
    ``post`` spans carry the sparse layers' counts (``SPARSE_STATS``) beside
    the state's, a decode round's ``sparse_pages_read`` never over ``topk``
    pages a (sequence, KV head, layer) nor over ``pages_held``;
    ``serve.cache_spec`` states a token's bytes WITH its share of a
    compressed row; ONE ``lightning.plan`` instant a program traced says
    which form it holds, ``sparse.plan`` says how a sparse layer attends
    (``masked`` in a chunk, ``gathered`` in a round off a TPU); the programs
    carry both mixers' scopes."""
    import jax.numpy as jnp

    from hetu_tpu.models.block import SPARSE_STATS, ChosenBlocks
    from hetu_tpu.models.minicpm_sala import (
        LIGHTNING, SPARSE, MiniCPMSALAConfig, MiniCPMSALAModel,
    )

    model = MiniCPMSALAModel(MiniCPMSALAConfig(
        vocab_size=97, hidden_size=32, num_layers=4,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE), num_heads=4,
        num_kv_heads=2, head_dim=8, lightning_heads=4, lightning_kv_heads=4,
        lightning_head_dim=8, lightning_chunk=8, ffn_size=64,
        sparse=ChosenBlocks(stride=2, kernel=4, block=4, topk=4,
                            init_blocks=1, local=4, dense_len=16),
        published_layers=8, first_layer=2, max_position=128,
        dtype=jnp.float32, param_dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=128,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    # K and V rows of 2 layers x 2 heads x 8 float32, and half a compressed
    # row a layer (one every 2 tokens)
    assert spec[3]["bytes_per_token"] == 2 * 2 * 8 * 4 * 2 + 2 * 2 * 8 * 4 // 2
    assert (spec[3]["state_layers"], spec[3]["cache_layers"]) == (2, 2)
    rounds = _named(events, "serve.decode.post")
    chunks = _named(events, "serve.prefill_chunk.post")
    assert rounds and chunks
    for e in rounds + chunks:
        assert set(SPARSE_STATS) <= set(e[3])
        assert e[3]["blocks_chosen"] <= e[3]["blocks_visible"]
    for e in chunks:
        assert e[3]["sparse_pages_read"] == 0
    # prompts of 5, 20 and 33 over a dense length of 16: two choose
    assert sum(e[3]["sparse_queries"] for e in chunks) == (20 + 33) * 2
    assert sum(e[3]["dense_queries"] for e in chunks) == 5 * 2
    for e in rounds:
        ids = e[3]
        assert ids["sparse_pages_read"] <= ids["pages_held"]
        assert ids["sparse_pages_read"] <= ids["sparse_queries"] * 2 * 4
        assert ids["sparse_queries"] + ids["dense_queries"] > 0
    assert any(e[3]["sparse_pages_read"] < e[3]["pages_held"]
               for e in rounds)
    plans = [e[3] for e in _named(events, "lightning.plan")]
    assert len(plans) == eng.compiled_executables()   # one a program traced
    assert {p["form"] for p in plans} == {"chunk", "step"}
    assert all(p["rule"] == "ops.ssm" and p["state_bytes_per_slot"]
               == 4 * 8 * 8 * 4 for p in plans)
    forms = {e[3]["form"] for e in _named(events, "sparse.plan")}
    assert forms == {"masked", "gathered"}
    from paged_programs import traced
    shared = ("hetu.lightning.proj", "hetu.lightning.norm", "hetu.ffn.dense",
              "hetu.sparse.compress", "hetu.sparse.select",
              "hetu.sparse.attend", "hetu.attn.full")
    for name, own, other in (
            ("decode", "hetu.lightning.step", "hetu.lightning.rule"),
            ("chunk", "hetu.lightning.rule", "hetu.lightning.step")):
        text = traced(eng, name, batch=4, chunk=8).lower().as_text(
            debug_info=True)
        assert all(scope in text for scope in shared + (own,)), name
        assert other not in text, name


def test_a_row_selecting_models_counts_plans_and_scopes(tmp_path):
    """A lightning indexer over a latent cache beside Kimi Delta Attention
    state layers under hyper-connections (ISSUE 58): both ``post`` spans
    carry the DSA layers' counts (``INDEX_STATS``) behind the expert
    layers', ``groups_chosen`` never over ``groups_visible`` nor over
    ``index_topk / index_kpool`` a query, ``index_kernel_queries`` 0 off a
    TPU; ``serve.cache_spec`` states a
    token's bytes as ONE latent and its share of a pooled key, a V pool of
    no width and the three state parts; ONE ``kda.plan``, ``index.plan``
    and ``mhc.plan`` instant a program traced; the programs carry the new
    scopes."""
    import jax.numpy as jnp

    from hetu_tpu.models.glm5_next import (
        DSA, INDEX_STATS, KDA, GLM5NextConfig, GLM5NextModel,
    )

    model = GLM5NextModel(GLM5NextConfig(
        vocab_size=97, hidden_size=32, num_layers=3,
        layer_types=(KDA, DSA, KDA),
        mlp_layer_types=("dense", "sparse", "sparse"), num_heads=4,
        head_dim=8, v_head_dim=8, q_lora_rank=16, kv_lora_rank=16,
        index_n_heads=2, index_head_dim=8, index_topk=16, index_kpool=4,
        index_rope_dim=4, index_query_block=4, kda_heads=4, kda_head_dim=8,
        kda_gate_rank=4, kda_chunk=8, kda_sub=4, ffn_size=64,
        expert_ffn_size=16, n_routed_experts=8, moe_topk=2, held=(0, 4),
        max_position=128, dtype=jnp.float32, param_dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0))
    with profiled(tmp_path):
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=128,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        _serve(ContinuousBatchingScheduler(eng))
    events = hetu_threads(tmp_path)[0]
    (spec,) = _named(events, "serve.cache_spec")
    # one latent of 16 float32 a token and a quarter of a pooled key of 8
    assert spec[3]["bytes_per_token"] == 16 * 4 + 8 * 4 // 4
    assert (spec[3]["k_width"], spec[3]["v_width"]) == (16, 0)
    assert (spec[3]["state_layers"], spec[3]["cache_layers"]) == (2, 1)
    assert {"state_conv_bytes", "state_delta_bytes",
            "state_open_bytes"} <= set(spec[3])
    rounds = _named(events, "serve.decode.post")
    chunks = _named(events, "serve.prefill_chunk.post")
    assert rounds and chunks
    for e in rounds + chunks:
        ids = e[3]
        assert set(INDEX_STATS) <= set(ids) and "moe_held" in ids
        assert ids["groups_chosen"] <= ids["groups_visible"]
        assert ids["groups_chosen"] <= 4 * (
            ids["sparse_queries"] + ids["dense_queries"])
        assert ids["index_kernel_queries"] == 0
    # prompts of 5, 20 and 33: positions from 19 on have over 4 groups
    assert sum(e[3]["sparse_queries"] for e in chunks) == 1 + 14
    assert sum(e[3]["dense_queries"] for e in chunks) == 5 + 19 + 19
    assert any(e[3]["groups_chosen"] < e[3]["groups_visible"]
               for e in rounds)
    for name in ("kda.plan", "index.plan", "mhc.plan"):
        plans = [e[3] for e in _named(events, name)]
        assert len(plans) == eng.compiled_executables(), name
    assert {p["form"] for p in (e[3] for e in _named(events, "kda.plan"))} \
        == {"chunk", "step"}
    for p in (e[3] for e in _named(events, "index.plan")):
        # a round gathers through the tables; a chunk by the rule
        # (``ops.index_kernel_why``: latents of 16 are no whole lane tiles)
        assert (p["form"], p["why"], p["groups"], p["rows"]) == (
            "gathered", "round" if p["query_block"] == 1 else "width", 4, 20)
    assert all(e[3]["streams"] == 4 for e in _named(events, "mhc.plan"))
    from paged_programs import traced
    shared = ("hetu.mhc.mix", "hetu.kda.proj", "hetu.kda.conv",
              "hetu.kda.norm", "hetu.dsa.proj", "hetu.index.proj",
              "hetu.index.pool", "hetu.index.select", "hetu.index.attend",
              "hetu.ffn.dense", "hetu.moe.experts")
    for name, own, other in (("decode", "hetu.kda.step", "hetu.kda.rule"),
                             ("chunk", "hetu.kda.rule", "hetu.kda.step")):
        text = traced(eng, name, batch=4, chunk=8).lower().as_text(
            debug_info=True)
        assert all(scope in text for scope in shared + (own,)), name
        assert other not in text, name


# ------------------------------------------ a trained expert model's counts

def _expert_model():
    import jax.numpy as jnp

    from hetu_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model

    model = DeepseekV3Model(DeepseekV3Config(
        vocab_size=97, hidden_size=32, num_layers=3, num_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, ffn_size=48, expert_ffn_size=16, n_routed_experts=8,
        moe_topk=2, held=(2, 4), expert_block_rows=8, ce_row_chunk=32,
        max_position=64, dtype=jnp.float32))
    batch = (np.random.default_rng(0).integers(0, 97, (2, 32)).astype(
        np.int32),)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0)), batch


def test_train_moe_instant_carries_each_steps_counts(tmp_path):
    """``train.moe``: one instant a finished step, written by a LATER
    ``run`` (the last step's is never written: nothing runs after it), with
    the step's number and the expert layers' counts as ids."""
    from hetu_tpu.models.deepseek_v3 import MOE_STEP_IDS

    model, variables, batch = _expert_model()
    ex = Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
    state = ex.init_state(variables)
    seen = []
    with profiled(tmp_path):
        for _ in range(4):
            state, metrics = ex.run("train", state, batch)
            jax.block_until_ready(metrics)   # the test's wait, not run()'s
            seen.append({k: np.asarray(v).item()
                         for k, v in metrics["moe"].items()})
    every = hetu_threads(tmp_path)[0]
    events = _named(every, "train.moe")
    assert [e[3]["step"] for e in events] == [1, 2, 3]
    # the step's own span carries the same number, and the instant of step n
    # is written by a later run: after span n, before span n + 2
    spans = _named(every, "train.step.train")
    assert [e[3] for e in spans] == [{"step": n} for n in (1, 2, 3, 4)]
    for e, own, later in zip(events, spans, spans[1:]):
        assert own[3]["step"] == e[3]["step"] and own[2] <= e[1] <= later[1]
    for e, want in zip(events, seen):
        ids = dict(e[3])
        assert set(ids) == {"step", *MOE_STEP_IDS}
        assert ids.pop("step") and ids == pytest.approx(want)
        assert ids["moe_held"] + ids["moe_absent"] == 2 * 32 * 2 * 2
        assert ids["moe_blocks_fwd"] == ids["moe_blocks_bwd"] \
            >= ids["moe_held"] / 8
        assert e[2] - e[1] < 1e6                 # an instant: no length
    assert 0 < events[-1][3]["router_bias_absmax"] <= 0.0031


@pytest.mark.parametrize("path", ["grouped", "cut"])
def test_train_moe_instant_says_what_the_grouped_path_computed(
        tmp_path, monkeypatch, path):
    """``moe_grouped``: the step's held pairs that the grouped path computed,
    all of them where the experts fit the rule's limit
    (``ops.moe_ops.held_expert_path``) and none where they pass it: a
    training step differentiates, and past the limit reverse mode walks
    the loop."""
    from hetu_tpu.ops import moe_ops

    # experts of 32 x 16: at the limit of what the kernels keep, or over it
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                        32 * 16 if path == "grouped" else 32 * 16 - 1)
    assert moe_ops.held_expert_path(64, 2, 4, 32, 16) == path
    model, variables, batch = _expert_model()
    ex = Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
    state = ex.init_state(variables)
    with profiled(tmp_path):
        for _ in range(3):
            state, metrics = ex.run("train", state, batch)
            jax.block_until_ready(metrics)
    events = _named(hetu_threads(tmp_path)[0], "train.moe")
    assert [e[3]["step"] for e in events] == [1, 2]
    for e in events:
        assert e[3]["moe_held"] > 0
        assert e[3]["moe_grouped"] == (e[3]["moe_held"]
                                       if path == "grouped" else 0)


def test_no_step_waits_for_its_counts():
    """``run`` only ASKS whether a queued step's counts are finished: while
    they are not, it issues the next step and keeps them queued; it never
    blocks on them."""
    model, variables, batch = _expert_model()
    ex = Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
    state = ex.init_state(variables)
    state, _ = ex.run("train", state, batch)
    (step, groups), = ex._groups
    assert step == 1 and set(groups) == {"moe"}

    class NotYet:
        def is_ready(self):
            return False

        def block_until_ready(self):
            raise AssertionError("run() waited for a step's counts")

        def __array__(self, *a, **kw):
            raise AssertionError("run() fetched unfinished counts")

    ex._groups[0] = (1, {"moe": {"moe_held": NotYet()}})
    state, _ = ex.run("train", state, batch)
    assert [s for s, _ in ex._groups] == [1, 2]
    # a model with no groups queues nothing
    gpt = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                             num_heads=2, ffn_size=64, max_position=32))
    ex2 = Executor(gpt.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
    s2 = ex2.init_state(gpt.init(jax.random.PRNGKey(0)))
    ex2.run("train", s2, (batch[0][:, :16] % 64,))
    assert ex2._groups == []


# ------------------------------- what a recomputed layer keeps (ISSUE 38)

@pytest.mark.parametrize("mesh_axes,tp", [(None, 1), ({"dp": 4}, 1),
                                          ({"dp": 2, "tp": 2}, 2)],
                         ids=["no-mesh", "dp4-tp1", "dp2tp2"])
def test_remat_plan_says_whether_the_reduction_is_kept(mesh_axes, tp):
    """One ``remat.plan`` instant per ``ops.remat`` call, when the step is
    TRACED: ``reduced`` is 1 where the mesh in context splits 'tp' and 0
    anywhere else.  A second run of the compiled step emits none."""
    import hetu_tpu as ht
    from hetu_tpu.parallel.mesh import mesh_context
    from hetu_tpu.parallel.strategies import MegatronLM

    model = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0, remat=True))
    params = model.init(jax.random.PRNGKey(0))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 97)
    mesh = ht.make_mesh(**mesh_axes) if mesh_axes else None
    if mesh is not None:
        params = jax.device_put(params, MegatronLM().shardings(params, mesh))
    loss_fn = model.lm_loss_fn()
    grad = jax.jit(jax.grad(lambda p: loss_fn(p, {}, (ids,), None, True)[0]))

    def plans(t):
        return [e["args"] for e in t.events if e["name"] == "remat.plan"]

    t = trace.enable()
    try:
        with mesh_context(mesh):
            jax.block_until_ready(grad(params))
            assert plans(t) == [{"reduced": int(tp > 1), "tp": tp}]
            jax.block_until_ready(grad(params))
            assert len(plans(t)) == 1
    finally:
        trace.disable()


# --------------- what a flash call's walk visits, and at which heads (ISSUE 40)

@pytest.mark.parametrize("window,kv_heads,live", [
    (None, 4, 10), (40, 4, 9), (40, 1, 9), (8, 2, 7), (128, 4, 10)],
    ids=["causal", "w40", "w40-one-kv-head", "w8-two-kv-heads", "w-eq-s"])
def test_flash_plan_says_the_window_the_kv_heads_and_the_tiles(window,
                                                               kv_heads,
                                                               live):
    """One ``flash.plan`` instant per kernel built, when the call is TRACED:
    beside the ids it had, ``window`` (0 for none), ``kv_heads``, and
    ``tiles_live`` beside ``tiles_causal``: what the walk visits and what a
    causal walk would, a call (all heads), and ``steps``, the steps of the
    call's grid (``tests/test_flash_attention.py`` holds a streamed call's
    to its longest live walk).  S = 128 in tiles of 32: the
    diagonal leaves 10 of 16 tiles a head; a window of 40 hides the corner
    tile (9), one of 8 the three below the subdiagonal (7), one as long as
    the keys nothing."""
    import jax.numpy as jnp

    from hetu_tpu.ops.pallas_kernels import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 32))
    k, v = (jax.random.normal(kk, (2, kv_heads, 128, 32)) for kk in ks[1:])
    grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window, block_q=32, block_k=32) ** 2),
        argnums=(0, 1, 2)))
    t = trace.enable()
    try:
        jax.block_until_ready(grad(q, k, v))
        plans = [e["args"] for e in t.events if e["name"] == "flash.plan"]
        jax.block_until_ready(grad(q, k, v))     # compiled: none per call
        assert len([e for e in t.events if e["name"] == "flash.plan"]) == 3
    finally:
        trace.disable()
    assert [p["kernel"] for p in plans] == ["fwd", "dkdv", "dq"]
    for p in plans:
        assert set(p) == {"kernel", "resident", "block_q", "block_k", "s_q",
                          "s_k", "d", "d_v", "window", "kv_heads",
                          "tiles_live", "tiles_causal", "steps"}
        assert (p["window"], p["kv_heads"]) == (window or 0, kv_heads)
        # resident at this size: the grid is a step a block a program owns,
        # and the dK/dV program of a KV head walks its group's heads itself
        assert p["steps"] == \
            2 * (kv_heads if p["kernel"] == "dkdv" else 4) * 4
        assert (p["tiles_live"], p["tiles_causal"]) == (8 * live, 80)
