"""The decode step's device time by the program's named scopes: the scope
of each operation, the reduction on a hand-built trace whose numbers are
worked out by hand and on a short trace recorded on a TPU v5e, the scopes
in the compiled step, and the readers of the per-layer metrics."""
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, trace

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
DATA = REPO / "chipbench" / "data"
METRICS = {"weights_ms.decode": "weights", "attention_ms.decode": "attention",
           "mlp_ms.decode": "mlp", "tp_scope_ms.decode": "tp_site",
           "unscoped_ms.decode": "unscoped"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---- a serialized protobuf, written field by field -----------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields) -> bytes:
    """(number, value) pairs: an int is a varint, bytes or str a
    length-delimited field."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def instr(name, opcode, op_name=None, called=()):
    f = [(scopes.INSTR_NAME, name), (scopes.INSTR_OPCODE, opcode)]
    if op_name is not None:
        f.append((scopes.INSTR_METADATA, msg((scopes.OP_NAME, op_name))))
    if called:
        f.append((scopes.INSTR_CALLED, b"".join(varint(c) for c in called)))
    return msg(*f)


def computation(cid, *instrs):
    return msg((scopes.COMPUTATION_ID, cid),
               *[(scopes.COMPUTATION_INSTRUCTIONS, i) for i in instrs])


def step_module(scoped: bool = True) -> bytes:
    """jit_step's HloModuleProto: an entry computation and a loop body
    (id 2)."""
    s = (lambda path: path) if scoped else (
        lambda path: "/".join(c for c in path.split("/")
                              if c not in scopes.SCOPES))
    entry = computation(
        1,
        instr("fusion.1", "fusion", s("jit(step)/embed/weights/convert")),
        instr("while.2", "while", s("jit(step)/layers/while"), (2, 3)),
        instr("fusion.6", "fusion", s("jit(step)/head/reduce_max")),
        instr("convert.7", "convert"))            # made by the compiler
    body = computation(
        2,
        instr("fusion.3", "fusion",
              s("jit(step)/layers/while/body/attention/dot_general")),
        instr("fusion.4", "fusion",
              s("jit(step)/layers/while/body/attention/tp_site/reduce_min")),
        instr("copy.5", "copy"))                  # inside the loop
    return msg((scopes.MODULE_COMPUTATIONS, entry),
               (scopes.MODULE_COMPUTATIONS, body))


def plane(pid, name, lines=(), metadata=(), stat_names=()):
    """lines: [(name, [(metadata id, start_us, end_us)])]; metadata:
    [(id, name, [(stat id, bytes)])]."""
    f = [(1, pid), (scopes.PLANE_NAME, name)]
    for li, (lname, evs) in enumerate(lines, 1):
        f.append((3, msg((1, li), (2, lname), (3, 0), *[
            (4, msg((1, m), (2, s * 10**6), (3, (e - s) * 10**6)))
            for m, s, e in evs])))
    for mid, mname, stats in metadata:
        value = msg((1, mid), (scopes.EVENT_METADATA_NAME, mname), *[
            (scopes.EVENT_METADATA_STATS,
             msg((scopes.STAT_METADATA_ID_REF, sid), (scopes.STAT_BYTES, b)))
            for sid, b in stats])
        f.append((scopes.PLANE_EVENT_METADATA,
                  msg((scopes.ENTRY_KEY, mid), (scopes.ENTRY_VALUE, value))))
    for sid, sname in stat_names:
        f.append((scopes.PLANE_STAT_METADATA, msg(
            (scopes.ENTRY_KEY, sid),
            (scopes.ENTRY_VALUE, msg((scopes.STAT_METADATA_ID, sid),
                                     (scopes.STAT_METADATA_NAME, sname))))))
    return msg(*f)


def xspace(scoped: bool = True) -> bytes:
    """One device: a 1000 us step program, nested ops in its loop, and a
    token feed; the window is 1100 us; the step's program in the metadata
    plane."""
    ops = ["jit_step(1234567890123)", "jit__feed(987654321)",
           "%fusion.1 = bf16[8] fusion(f32[8] %p)",
           "%while.2 = (s32[]) while(s32[] %a)",
           "%fusion.3 = f32[8] fusion(f32[8] %b)",
           "%fusion.4 = f32[8] fusion(f32[8] %c)",
           "%copy.5 = f32[8] copy(f32[8] %d)",
           "%fusion.6 = f32[8] fusion(f32[8] %e)",
           "%convert.7 = bf16[8] convert(f32[8] %f)",
           "%mystery.8 = f32[8] fusion(f32[8] %g)",     # not in the program
           "%copy.1 = s32[4] copy(s32[4] %h)"]          # the feed's
    ids = {n: i for i, n in enumerate(ops, 1)}
    e = lambda n, s, t: (ids[n], s, t)
    modules = [e(ops[0], 0, 1000), e(ops[1], 1000, 1100)]
    xla_ops = [e(ops[2], 0, 100), e(ops[3], 100, 700), e(ops[4], 150, 300),
               e(ops[5], 300, 400), e(ops[6], 400, 450), e(ops[7], 700, 800),
               e(ops[8], 800, 900), e(ops[9], 900, 950),
               e(ops[10], 1000, 1050)]
    device = plane(1, "/device:TPU:0",
                   [("XLA Modules", modules), ("XLA Ops", xla_ops)],
                   [(i, n, []) for n, i in ids.items()])
    host = plane(2, "/host:CPU", [("python3", [(1, 0, 1100)])],
                 [(1, trace.WINDOW, [])])
    meta = plane(3, scopes.METADATA_PLANE, (),
                 [(1, ops[0], [(7, msg((scopes.HLO_MODULE,
                                         step_module(scoped))))])],
                 [(7, scopes.HLO_PROTO_STAT)])
    return msg(*[(scopes.SPACE_PLANES, p) for p in (device, host, meta)])


# ---- the scope of an operation -------------------------------------------

def test_innermost_scope():
    assert scopes.innermost(
        "jit(step)/layers/while/body/closed_call/checkpoint/weights/"
        "convert_element_type") == "weights"
    assert scopes.innermost("jit(step)/layers/while/body/attention/"
                            "tp_site/jit(encode_wire)/wire_encode/"
                            "pallas_call:") == "tp_site"
    assert scopes.innermost("jit(step)/jit(take_along_axis)/gather") is None
    assert scopes.innermost("jit(step)/weights_x/headless") is None


def test_program_scopes():
    """Own op_name first, else that of the instruction that runs the
    computation holding it; a top-level op with no op_name has none."""
    p = scopes.program_scopes(step_module())
    assert {n: s for n, (_, s) in p.items()} == {
        "fusion.1": "weights", "while.2": "layers", "fusion.6": "head",
        "convert.7": None, "fusion.3": "attention", "fusion.4": "tp_site",
        "copy.5": "layers"}
    assert p["while.2"][0] == "while"


def test_scope_seconds_by_hand():
    raw = xspace()
    programs = scopes.trace_programs(raw)
    assert list(programs) == ["jit_step(1234567890123)"]
    r = trace.reduce(ProfileData.from_serialized_xspace(raw))
    assert r.busy_s == pytest.approx([1000e-6])
    assert r.op_s["jit_step(123456)/while.2"] == pytest.approx(300e-6)
    secs, found = scopes.scope_seconds(r.op_s, programs)
    assert secs == pytest.approx({
        "weights": 100e-6, "attention": 150e-6, "mlp": 0.0,
        "tp_site": 100e-6, "embed": 0.0,
        "layers": 300e-6 + 50e-6,       # the loop's own time and its copy
        "head": 100e-6,
        "unscoped": 100e-6 + 50e-6})    # convert.7, and mystery.8 unknown
    step = sum(v for k, v in r.op_s.items() if k.startswith("jit_step"))
    assert sum(secs.values()) == pytest.approx(step)
    assert found == pytest.approx(step - 50e-6)
    # the feed program stays out; with it, the whole busy time
    assert sum(secs.values()) + r.op_s["jit__feed(987654)/copy.1"] == \
        pytest.approx(r.busy_s[0])


def traced_root(tmp_path: Path, raw: bytes) -> Path:
    """A copy of the metric readers beside a trace where the harness
    leaves one."""
    shutil.copytree(REPO / "chipbench" / "metrics",
                    tmp_path / "chipbench" / "metrics")
    d = tmp_path / harness.TRACE_DIR / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return tmp_path


def measures(raw: bytes, mode: str = "decode", steps: int = 2):
    return harness.Measures(
        trace=trace.reduce(ProfileData.from_serialized_xspace(raw)),
        work={"mode": mode, "steps": steps}, peaks={}, chips=1)


def test_metric_readers(tmp_path, capsys):
    root = traced_root(tmp_path, xspace())
    m = measures(xspace())
    got = {n: harness.read_metric(root, n)(m) for n in METRICS}
    assert got == pytest.approx({
        "weights_ms.decode": 0.05, "attention_ms.decode": 0.075,
        "mlp_ms.decode": 0.0, "tp_scope_ms.decode": 0.05,
        "unscoped_ms.decode": 0.075})
    said = capsys.readouterr().out
    assert said.count("[chipbench] scopes, ms a step over 2 steps") == 1
    assert "found in the program 94.737% of jit_step" in said
    assert harness.read_metric(root, "mlp_ms.decode")(
        measures(xspace(), mode="train")) is None


def test_no_scope_no_reading(tmp_path, capsys):
    """A program that names no scope (the parent of this change) reads
    nothing, as does a run whose trace is gone."""
    root = traced_root(tmp_path, xspace(scoped=False))
    m = measures(xspace(scoped=False))
    assert all(harness.read_metric(root, n)(m) is None for n in METRICS)
    assert "the program names no scope" in capsys.readouterr().out
    gone = tmp_path / "gone"
    shutil.copytree(REPO / "chipbench" / "metrics",
                    gone / "chipbench" / "metrics")
    assert harness.read_metric(gone, "weights_ms.decode")(m) is None


# ---- the compiled step ---------------------------------------------------

@pytest.fixture(scope="module")
def compiled():
    """The rehearsal's decode step compiled on one and on four virtual CPU
    devices: {model axis size: {op: (opcode, scope, has an op_name)}}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, str(HERE / "scoped_step.py"),
                        "1", "4"], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return {int(k): v for k, v in
            json.loads(p.stdout.strip().splitlines()[-1]).items()}


@pytest.mark.parametrize("tp", [1, 4])
def test_compiled_step_names_every_scope(compiled, tp):
    assert {s for _, s, _ in compiled[tp].values()} >= set(scopes.SCOPES)


def test_every_named_op_has_a_scope(compiled):
    """On one device every operation that carries an op_name, other than
    the step's parameters, lies under one of the seven scopes: a scope
    a change loses shows here before it shows as unscoped time."""
    lost = sorted(n for n, (op, s, named) in compiled[1].items()
                  if named and s is None and op != "parameter")
    assert lost == []


def test_collectives_lie_under_tp_site(compiled):
    """On four devices every collective is the tensor-parallel site's,
    but the greedy head's all-gathers of each rank's best logit and its
    index: ``tp_site_ms`` counts those by opcode, ``tp_scope_ms`` not."""
    coll = {n: (op, s) for n, (op, s, _) in compiled[4].items()
            if op.startswith(COLLECTIVES)}
    assert any(s == "tp_site" for _, s in coll.values())
    outside = {n: c for n, c in coll.items() if c[1] != "tp_site"}
    assert outside and all(c == ("all-gather", "head")
                           for c in outside.values()), outside


def test_metric_files_read_scopes_the_program_emits(compiled):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {m["name"] for m in spec["per_layer"]}
    assert set(METRICS) <= names
    emitted = {s for _, s, _ in compiled[1].values()} | {scopes.UNSCOPED}
    for name, scope in METRICS.items():
        text = (REPO / "chipbench" / "metrics" / f"{name}.py").read_text()
        assert f'SCOPE = "{scope}"' in text
        assert scope in emitted


# ---- a trace recorded on the chip ----------------------------------------

def test_recorded_scoped_chip_trace():
    """0.29 s of the decode cell on one TPU v5e: three decode steps of
    Qwen3-14B (3 layers, batch 32) with the program's scopes, and the
    programs in the trace's metadata plane (recorded by a
    ``--seconds 0.25 --trace 1`` run whose trace was kept)."""
    raw = gzip.open(DATA / "qwen3-14b.decode-b32.v5e.xplane.pb.gz").read()
    r = trace.reduce(ProfileData.from_serialized_xspace(raw))
    programs = scopes.trace_programs(raw)
    secs, found = scopes.scope_seconds(r.op_s, programs)
    step = sum(v for k, v in r.op_s.items() if k.startswith("jit_step("))
    feed = sum(v for k, v in r.op_s.items() if k.startswith("jit__feed("))
    # every operation of the step is in its program; the scopes and
    # unscoped are the step's own time, and with the feed the busy time
    # (own times exceed the union by 448 ns, where two operations
    # overlap without one nesting in the other)
    assert found == pytest.approx(step, rel=1e-12)
    assert sum(secs.values()) == pytest.approx(step, rel=1e-12)
    assert step + feed == pytest.approx(r.busy_s[0], rel=1e-5)
    assert max(secs, key=secs.get) == "weights"
    per_step_ms = {k: 1e3 * v / 3 for k, v in secs.items()}
    assert per_step_ms == pytest.approx({
        "weights": 54.571, "attention": 7.957, "mlp": 2.162,
        "tp_site": 0.224, "embed": 0.003, "layers": 5.990, "head": 2.072,
        "unscoped": 13.919}, abs=0.001)
    # under no scope: the stacked per-layer weight casts the compiler
    # hoisted out of the layer loop, with no op_name, the costliest w1,
    # w2 and w3 (3.4 ms a step each), and the stacked caches' copy-out
    step_ops, = [p for m, p in programs.items() if m.startswith("jit_step(")]
    unscoped = sorted(((v, k.partition("/")[2]) for k, v in r.op_s.items()
                       if k.startswith("jit_step(")
                       and step_ops[k.partition("/")[2]][1] is None),
                      reverse=True)
    assert {op for _, op in unscoped[:3]} == {"convert.102", "convert.103",
                                              "convert.104"}
    casts_and_copies = sum(v for v, op in unscoped
                           if step_ops[op][0] in ("convert", "copy"))
    assert casts_and_copies > 0.999 * secs["unscoped"]
