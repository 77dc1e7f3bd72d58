"""The program's spans and named scopes in a trace (bench/program_trace.py):
the reductions on synthesized traces, the readers of the nine metrics on
them, and the program's spans in a real profile of a small trainer."""

from types import SimpleNamespace

import pytest

from bench import program_trace as pt
from bench import tracing
from bench.program_trace import Op, ProgramTrace
from bench.tracing import Event
from benchkit import BENCH

MS = 1e6   # ns
WINDOW = (0.0, 100 * MS)


@pytest.mark.parametrize("stack,scope", [
    ("jit(train_step)/jit(main)/fl_client/conv_general_dilated",
     "fl_client"),
    # the downlink codec's backward rule runs inside the uplink codec's VJP
    ("jit(loss)/transpose(jvp(fl_uplink_codec))/fl_downlink_codec/"
     "jit(sort)/sort", "fl_downlink_codec"),
    ("jit(train_step)/transpose(jvp(fl_uplink_codec))/"
     "vmap(fl_downlink_codec)/jit(sort)/sort", "fl_downlink_codec"),
    ("jit(train_step)/fl_optimizer/add", "fl_optimizer"),
    ("jit(train_step)/fl_serverless/add", ""),
    ("jit(fold_in)/threefry2x32", ""),
    ("", ""),
])
def test_innermost_scope(stack, scope):
    assert pt.innermost_scope(stack) == scope


def ops():
    # a while op of the uplink codec holding its body (a kernel of the same
    # scope and a client op), a downlink sort, an unscoped copy, and one op
    # crossing the window's end
    return [Op("fusion.1", 5 * MS, 15 * MS, "fl_client"),
            Op("while.4", 20 * MS, 50 * MS, "fl_uplink_codec"),
            Op("lloyd_update_kernel.6", 25 * MS, 35 * MS, "fl_uplink_codec"),
            Op("fusion.9", 40 * MS, 45 * MS, "fl_client"),
            Op("sort", 55 * MS, 75 * MS, "fl_downlink_codec"),
            Op("copy.3", 75 * MS, 80 * MS, ""),
            Op("add.2", 95 * MS, 110 * MS, "fl_optimizer")]


def program():
    # two updates: round assembly, then the step's dispatch inside the round
    return [Event("trainer.round", 0, 30 * MS),
            Event("executor.dispatch", 20 * MS, 30 * MS),
            Event("trainer.round", 50 * MS, 90 * MS),
            Event("executor.dispatch", 80 * MS, 90 * MS)]


def test_scope_self_time_counts_innermost_and_nesting_once():
    t = pt.scope_self_ns(ops(), WINDOW)
    # the while's 30 ms hold 15 ms of nested ops; 5 of them are the client's
    assert t == pytest.approx({"fl_client": 15 * MS,
                               "fl_uplink_codec": 25 * MS,
                               "fl_downlink_codec": 20 * MS,
                               "": 5 * MS, "fl_optimizer": 5 * MS})


def test_scope_self_times_and_remainder_add_up_to_busy_time():
    evs = [Event(o.name, o.start, o.end) for o in ops()]
    assert sum(pt.scope_self_ns(ops(), WINDOW).values()) == \
        pytest.approx(tracing.busy_ns(evs, WINDOW))


def test_intersect_and_length():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert pt.intersect(a, b) == [(5, 10), (20, 25), (28, 30), (40, 45)]
    assert pt.length(pt.intersect(a, b)) == 17
    assert pt.intersect(a, []) == []


def test_assembly_and_dispatch_host_time():
    assembly, dispatch = pt.assembly_dispatch_ns(program(), WINDOW)
    assert (assembly, dispatch) == (50 * MS, 20 * MS)
    # a window that cuts the second round short
    assembly, dispatch = pt.assembly_dispatch_ns(program(), (0, 85 * MS))
    assert (assembly, dispatch) == (50 * MS, 15 * MS)


def test_idle_is_split_by_interval_intersection():
    evs = [Event(o.name, o.start, o.end) for o in ops()]
    # idle: 0..5, 15..20, 50..55, 80..95
    assert tracing.gaps(evs, WINDOW) == [
        (0, 5 * MS), (15 * MS, 20 * MS), (50 * MS, 55 * MS),
        (80 * MS, 95 * MS)]
    assembly, dispatch = pt.idle_split_ns(evs, program(), WINDOW)
    # in rounds and outside dispatch: 0..5, 15..20, 50..55; in dispatch:
    # 80..90; 90..95 lies in no round
    assert (assembly, dispatch) == (15 * MS, 10 * MS)
    idle = WINDOW[1] - tracing.busy_ns(evs, WINDOW)
    assert assembly + dispatch <= idle


def test_idle_split_never_exceeds_idle_time():
    evs = [Event("fusion.1", 10 * MS, 20 * MS)]
    # a dispatch span outside any round and one overlapping a round's end
    prog = [Event("trainer.round", 0, 40 * MS),
            Event("executor.dispatch", 30 * MS, 60 * MS),
            Event("executor.dispatch", 70 * MS, 80 * MS)]
    assembly, dispatch = pt.idle_split_ns(evs, prog, WINDOW)
    assert (assembly, dispatch) == (20 * MS, 40 * MS)
    assert assembly + dispatch <= WINDOW[1] - tracing.busy_ns(evs, WINDOW)


READERS = ("round_assembly_ms", "step_dispatch_ms", "idle_in_assembly_share",
           "idle_in_dispatch_share", "client_ms", "uplink_codec_ms",
           "server_ms", "downlink_codec_ms", "optimizer_ms")


def _ctx(prog_trace, updates=2):
    from bench import harness
    evs = [Event(o.name, o.start, o.end) for o in ops()]
    ctx = SimpleNamespace(
        trace=tracing.Trace({"/device:TPU:0": evs},
                            [Event("window", *WINDOW)]),
        window=WINDOW, updates=updates, chips=1)
    ctx.program_trace = prog_trace
    return ctx, {n: harness.load_module(BENCH / "metrics" / f"{n}.py",
                                        f"test_reader_{n}") for n in READERS}


def test_readers_on_a_synthesized_trace():
    ctx, readers = _ctx(ProgramTrace({"/device:TPU:0": ops()}, program(),
                                     [WINDOW]))
    got = {n: r.read(ctx) for n, r in readers.items()}
    assert got == pytest.approx({
        "round_assembly_ms": 25.0, "step_dispatch_ms": 10.0,
        "idle_in_assembly_share": 15.0, "idle_in_dispatch_share": 10.0,
        "client_ms": 7.5, "uplink_codec_ms": 12.5, "server_ms": None,
        "downlink_codec_ms": 10.0, "optimizer_ms": 2.5})


def test_readers_return_none_without_the_programs_marks():
    """A program with no spans and no scopes (or no trace file found)
    yields no reading, and no reader raises."""
    bare = ProgramTrace({"/device:TPU:0": [o._replace(scope="")
                                           for o in ops()]}, [], [WINDOW])
    for prog_trace in (bare, None):
        ctx, readers = _ctx(prog_trace)
        assert {n: r.read(ctx) for n, r in readers.items()} == \
            dict.fromkeys(READERS)


def test_profiled_rounds_yield_the_programs_spans(tmp_path, cpu_jax):
    """Three ``round()`` calls of a small trainer under the profiler, on
    the CPU: the trace holds three ``trainer.round`` and three
    ``executor.dispatch`` spans, each dispatch inside its round, and ``of``
    finds the file by the benchmark's window."""
    import jax
    from repro.core.quantizer import PQConfig
    from repro.data.synthetic import make_federated_image_data
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    data = make_federated_image_data(num_clients=4, seed=0)
    model = FemnistCNN(pq=PQConfig(num_subvectors=288, num_clusters=4,
                                   kmeans_iters=2), lam=1e-4, client_batch=4)
    tr = FederatedTrainer(model, sgd(0.03), data, cohort=2, client_batch=4)
    key = jax.random.PRNGKey(0)
    state = tr.init_state(key)
    state, _ = tr.round(state, key)            # compile outside the trace
    trace_dir = tmp_path / "out" / "trace" / "cell"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                state, _ = tr.round(state, key)
            jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    path = next(trace_dir.glob("**/*.xplane.pb"))
    got = pt.load(str(path), 1)
    rounds = [e for e in got.program if e.name == "trainer.round"]
    dispatch = [e for e in got.program if e.name == "executor.dispatch"]
    assert len(rounds) == 3 and len(dispatch) == 3
    for r, d in zip(sorted(rounds), sorted(dispatch)):
        assert r.start <= d.start < d.end <= r.end
    assert len(got.windows) == 1
    reader = tmp_path / "metrics" / "reader.py"
    ctx = SimpleNamespace(window=got.windows[0], chips=1)
    assert pt.of(ctx, str(reader)) == got
    stale = SimpleNamespace(window=(0.0, 1.0), chips=1)
    assert pt.of(stale, str(reader)) is None


# ---------------------------------------------------------------------------
# the modules' HLO on the metadata plane, in the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num, value):
    """One serialized field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instr(name, op_name="", calls=()):
    meta = _field(1, "add") + _field(2, op_name) if op_name else b""
    packed = b"".join(_varint(c) for c in calls)
    return _field(1, name) + _field(2, "fusion") + \
        (_field(7, meta) if op_name else b"") + \
        (_field(38, packed) if calls else b"")


def _comp(cid, *instrs):
    return _field(1, f"c{cid}") + b"".join(_field(2, i) for i in instrs) + \
        _field(5, cid)


def _xspace():
    # the step module: a fusion with its own stack, one whose stack XLA
    # dropped (its fused computation 7 holds the downlink codec's ops), a
    # copy with none; an unscoped eager module beside it
    step = _field(1, _field(1, "jit_train_step") + _field(3, _comp(
        1,
        _instr("fusion.3", "jit(train_step)/transpose(jvp(fl_uplink_codec))"
                           "/vmap(fl_downlink_codec)/gather"),
        _instr("fusion.5", calls=(7,)),
        _instr("copy-start"),
        _instr("while.2", "jit(train_step)/jvp(fl_client)/while"))) +
        _field(3, _comp(7, _instr("param_0"), _instr(
            "reshape.211", "jit(train_step)/transpose(jvp(fl_uplink_codec))"
                           "/vmap(fl_downlink_codec)/reshape"))))
    fold = _field(1, _field(1, "jit_fold_in") + _field(3, _comp(
        1, _instr("threefry2x32", "jit(fold_in)/threefry2x32"))))

    def module(label, hlo):
        stat = _field(1, 3) + _field(6, hlo)
        return _field(4, _field(1, 9) + _field(2, _field(1, 9) +
                                               _field(2, label) +
                                               _field(5, stat)))

    meta = _field(1, 0) + _field(2, "/host:metadata") + \
        module("jit_train_step(11)", step) + module("jit_fold_in(12)", fold) \
        + _field(5, _field(1, 3) + _field(2, _field(1, 3) +
                                          _field(2, "Hlo Proto")))
    other = _field(1, 2) + _field(2, "/device:TPU:0") + _field(3, b"\x08\x01")
    return _field(1, other) + _field(1, meta)


def test_module_scopes_read_the_hlo_on_the_metadata_plane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert pt.module_scopes(str(path)) == {
        "jit_train_step(11)": {"fusion.3": "fl_downlink_codec",
                               "fusion.5": "fl_downlink_codec",
                               "copy-start": "", "while.2": "fl_client",
                               "param_0": "",
                               "reshape.211": "fl_downlink_codec"},
        "jit_fold_in(12)": {"threefry2x32": ""}}


def test_ops_take_the_scope_of_their_module_and_instruction():
    scopes = {"jit_train_step(11)": {"fusion.3": "fl_downlink_codec",
                                     "while.2": "fl_client"},
              "jit_fold_in(12)": {"fusion.3": ""}}
    modules = [Event("jit_fold_in(12)", 0, 10 * MS),
               Event("jit_train_step(11)", 20 * MS, 60 * MS)]
    ops = [Event("%fusion.3 = f32[4]{0} fusion(...)", 1 * MS, 2 * MS),
           Event("%while.2 = (s32[]) while(...)", 25 * MS, 40 * MS),
           Event("%fusion.3 = f32[8]{0} fusion(...)", 30 * MS, 35 * MS),
           Event("%copy.1 = f32[8]{0} copy(...)", 45 * MS, 46 * MS),
           Event("%fusion.3 = f32[4]{0} fusion(...)", 70 * MS, 71 * MS)]
    assert pt.attribute(ops, modules, scopes) == [
        Op("fusion.3", 1 * MS, 2 * MS, ""),
        Op("while.2", 25 * MS, 40 * MS, "fl_client"),
        Op("fusion.3", 30 * MS, 35 * MS, "fl_downlink_codec"),
        Op("copy.1", 45 * MS, 46 * MS, ""),
        Op("fusion.3", 70 * MS, 71 * MS, "")]
