"""The trace reduction on a hand-built trace whose numbers are worked out
by hand, and on a short trace recorded on a TPU v5e."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = Path(__file__).resolve().parents[2] / "chipbench" / "data"


def xspace(planes) -> ProfileData:
    """planes: [(plane name, {line name: [(event, start_us, end_us)]})]."""
    out = []
    for pi, (pname, lines) in enumerate(planes, 1):
        names = sorted({e for evs in lines.values() for e, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = [f'id: {pi} name: "{pname}"']
        for li, (lname, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {ids[e]} offset_ps: {s * 10**6} "
                f"duration_ps: {(t - s) * 10**6} }}" for e, s, t in evs)
            body.append(f'lines {{ id: {li} name: "{lname}" timestamp_ns: 0 '
                        f"{ev} }}")
        body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in ids.items()]
        out.append("planes { " + " ".join(body) + " }")
    return ProfileData.from_text_proto("\n".join(out))


def two_devices():
    d0 = {"XLA Ops": [("wire_encode.1", 0, 100), ("fusion.2", 100, 300),
                      ("all-to-all.3", 250, 400), ("wire_decode.4", 600, 700),
                      ("fusion.9", 1100, 1200)]}
    d1 = {"XLA Ops": [("wire_encode.1", 0, 200), ("fusion.2", 200, 800)]}
    host = {"python": [(trace.WINDOW, 0, 1000), ("wait", 350, 650),
                       ("submit", 650, 1000)]}
    return xspace([("/device:TPU:0", d0), ("/device:TPU:1", d1),
                   ("/host:CPU", host)])


def test_reduce_by_hand():
    r = trace.reduce(two_devices(), spans=("wait", "submit"))
    assert r.window_s == pytest.approx(1000e-6)
    assert r.devices == ["/device:TPU:0", "/device:TPU:1"]
    # device 0: [0, 400) and [600, 700); the op after the window is out
    assert r.busy_s == pytest.approx([500e-6, 800e-6])
    assert r.mean_busy_s == pytest.approx(650e-6)
    assert r.codec_s == pytest.approx([200e-6, 200e-6])
    assert r.collective_s == pytest.approx([150e-6, 0.0])
    # codec + collectives as a union: [0, 100) + [250, 400) + [600, 700)
    assert r.tp_site_s == pytest.approx([350e-6, 200e-6])
    assert r.op_count["wire_encode"] == pytest.approx(1.0)
    assert r.op_count["wire_decode"] == pytest.approx(0.5)
    assert r.op_s["fusion.2"] == pytest.approx((200e-6 + 600e-6) / 2)
    # gaps: d0 [400, 600) under "wait", [700, 1000) under "submit";
    # d1 [800, 1000) under "submit"
    assert [g[0] for g in r.idle_gaps] == ["submit", "wait", "submit"]
    assert [g[1] for g in r.idle_gaps] == pytest.approx(
        [300e-6, 200e-6, 200e-6])
    assert r.top_ops(1) == [["fusion.2", pytest.approx(400e-6)]]


def test_window_defaults_to_device_extent():
    pd = xspace([("/device:TPU:0",
                  {"XLA Ops": [("fusion.1", 10, 20), ("fusion.2", 30, 50)]})])
    r = trace.reduce(pd)
    assert r.window_s == pytest.approx(40e-6)
    assert r.busy_s == pytest.approx([30e-6])
    assert r.idle_gaps == [("untracked", pytest.approx(10e-6))]


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(xspace([("/host:CPU", {"t": [("x", 0, 1)]})]))


def test_nested_operations_and_hlo_names():
    """A while spans its body's operations: its own time excludes them.
    On a TPU an event is named by its whole HLO instruction."""
    hlo = "%wire_encode.3 = u8[4,8320]{1,0} custom-call(f32[4,8192] %p)"
    assert trace.op_name(hlo) == "wire_encode.3"
    assert trace.op_name("fusion.2") == "fusion.2"
    pd = xspace([("/device:TPU:0", {"XLA Ops": [
        ("%while.1 = (s32[]) while(s32[] %a)", 0, 100),
        ("%fusion.2 = f32[8] fusion(f32[8] %b)", 10, 40),
        (hlo, 50, 90)]})])
    r = trace.reduce(pd)
    assert r.op_s == pytest.approx({"while.1": 30e-6, "fusion.2": 30e-6,
                                    "wire_encode.3": 40e-6})
    assert r.busy_s == pytest.approx([100e-6])
    assert r.codec_s == pytest.approx([40e-6])
    assert r.op_count == {"while": 1, "fusion": 1, "wire_encode": 1}


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert trace.clip([(0, 5), (6, 9)], 2, 8) == [(2, 5), (6, 8)]
    assert trace.base_name("wire_encode.12") == "wire_encode"
    assert trace.base_name("all-to-all") == "all-to-all"


def recorded():
    """0.31 s of the decode cell on one TPU v5e: three decode steps of
    GLM-4-9B (5 layers, batch 32) and the harness's host spans."""
    import gzip
    raw = gzip.open(DATA / "glm4-9b.decode-b32.v5e.xplane.pb.gz").read()
    return ProfileData.from_serialized_xspace(raw)


def modules(pd):
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    return [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]


def host_spans(pd):
    """(name, start) of the harness's spans in the recording, in order:
    the Python thread's annotations inside the window and not inside
    another one (JAX's own nest inside the harness's; the profiler's
    Python events are named ``$file:line function``)."""
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python3":
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    (_, lo, hi), = [e for e in evs if e[0] == trace.WINDOW]
    inner = [e for e in evs if lo <= e[1] and e[2] <= hi
             and e[0] != trace.WINDOW and not e[0].startswith("$")]
    top = [e for e in inner if not any(
        o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inner)]
    return sorted(((n, s) for n, s, _ in top), key=lambda t: t[1])


def test_recorded_chip_trace():
    from chipbench import counts
    pd = recorded()
    spans = host_spans(pd)
    names = sorted({n for n, _ in spans})
    assert len(names) == 4 and "wait" in names and "feed" in names
    r = trace.reduce(pd, spans=names)
    # the window is the harness's host span: 308,389,999 ns
    assert r.window_s == pytest.approx(0.308389999, abs=1e-9)
    assert r.devices == ["/device:TPU:0"]
    # busy: the three decode steps and three token feeds the XLA Modules
    # line shows, less the gaps between operations inside them
    mods = modules(pd)
    assert [m[0].split("(")[0] for m in mods] == ["jit_step", "jit__feed"] * 3
    in_modules = sum(d for _, _, d in mods) / 1e9
    assert 0.999 * in_modules < r.busy_s[0] <= in_modules
    # 11 all-reduces a step (embedding + 2 per layer), each 2 encodes and
    # 2 decodes: 66 of each kernel in three steps
    glm4_l5 = counts.Dims(d_model=4096, n_heads=32, n_kv_heads=2,
                          head_dim=128, d_ff=13696, vocab=151552, n_layers=5)
    per_step = counts.forward_codec(glm4_l5, 32, 1, 8, 128)
    assert r.op_count["wire_encode"] == r.op_count["wire_decode"] == \
        3 * per_step["encode_calls"] == 66
    # one chip: no collective, the TP site is the codec alone
    assert r.collective_s == [0.0]
    assert r.tp_site_s == pytest.approx(r.codec_s)
    assert 0 < r.codec_s[0] < 0.002
    # the longest gap: window start to the first step, under the span
    # that submits the first step
    name, gap = r.idle_gaps[0]
    first_step = mods[0][1] / 1e9
    window_start = first_step - gap
    assert name == spans[0][0] and gap == pytest.approx(0.03024, abs=1e-5)
    assert sum(g for _, g in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s[0], abs=1e-6)
    assert window_start > 0
    # the costliest operation: the whole embedding table cast to
    # bfloat16 in every step (about 9.6 ms each), named in its program
    top = r.top_ops(1)[0]
    assert top[0] == "jit_step(166998)/convert_reduce_fusion.7"
    assert top[1] == pytest.approx(3 * 0.0096, rel=0.01)
