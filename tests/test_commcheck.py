"""commcheck self-tests: the analyzer must catch what it was built for.

Three groups:

* **mutation fixtures** — every deliberately broken input in
  ``repro.analysis.mutations`` must fire exactly its rule (a checker
  that never fires is indistinguishable from one that works);
* **clean passes** — the live protocols, the shipped wire layouts and
  the shipped (config x policy x mesh) launch pairs must come back
  clean, and the property test proves the wire layout is a partition
  for *random* configs, not just the swept grid;
* **launch wiring** — the fail-fast guard raises ``CommCheckError``
  for fused-scheme launches the RDMA kernels cannot serve (on TPU),
  stays quiet off-TPU where the XLA emulation runs instead, and the
  CLI entry points exit 0 on the shipped repo.
"""
import pytest

from _hyp import given, settings, st
from repro.analysis import (choreography, commcheck, layout, mutations,
                            sites, vmem)
from repro.analysis.report import (ERROR, RULES, WARNING, CheckReport,
                                   CommCheckError)
from repro.core.comm_config import CommConfig
from repro.core.policy import CommPolicy, paper_policy, with_scheme

# ---------------------------------------------------------------------------
# mutation fixtures
# ---------------------------------------------------------------------------


# Fixtures whose rule's reachable real-world diagnostic is
# warning-severity: the store layout pads every flat length to the fsdp
# axis by construction, so SITE-QGRAD-ALIGN's divisibility *error* is
# defensive-only and a real model can only trip the group-padding lint.
WARN_FIXTURES = {"qgrad_misaligned"}


@pytest.mark.parametrize("name", sorted(mutations.FIXTURES))
def test_mutation_fixture_fires_its_rule(name):
    fn, rule = mutations.FIXTURES[name]
    diags = fn()
    want = WARNING if name in WARN_FIXTURES else ERROR
    fired = sorted({d.rule for d in diags if d.severity == want})
    assert rule in fired, (f"fixture {name}: wanted {rule} at {want} "
                           f"severity, fired {fired}")


def test_selftest_runner_agrees():
    passed, failed = mutations.run_selftest()
    assert not failed, failed
    assert len(passed) == len(mutations.FIXTURES)


def test_every_rule_has_a_fixture_or_known_exemption():
    """A rule nothing can fire is dead weight — keep the map honest."""
    covered = {rule for _, rule in mutations.FIXTURES.values()}
    # exercised elsewhere: LAYOUT-LANES is warning-severity (asserted
    # below), VMEM-BLOCK by the static sweep contract test, SITE-SEGMENT
    # by tests/test_policy_engine.py segmentation tests, SITE-FUSED-MESH
    # by test_fused_guard_raises_on_tpu, SITE-TRACE by the trace lane.
    exempt = {"LAYOUT-LANES", "VMEM-BLOCK", "SITE-SEGMENT",
              "SITE-FUSED-MESH", "SITE-TRACE"}
    assert set(RULES) - covered == exempt


# ---------------------------------------------------------------------------
# clean passes over the shipped repo
# ---------------------------------------------------------------------------


def test_live_protocols_clean():
    diags, checked = choreography.check_choreography(commcheck.TP_VALUES)
    assert checked > 0 and diags == []


def test_layout_sweep_clean():
    diags, checked = layout.check_layouts()
    assert checked > 0
    assert [d for d in diags if d.severity == ERROR] == []


def test_vmem_static_clean():
    diags, checked = vmem.check_vmem_static()
    assert checked > 0 and diags == []


def test_core_report_passes():
    assert commcheck.core_report().ok


def test_launch_report_shipped_pair_clean():
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("qwen3-14b")
    mesh_shape = {"data": 2, "model": 4}
    plan = make_plan(cfg, tp=4, fsdp=2)
    for pname, pol in commcheck.shipped_policies().items():
        rep = commcheck.launch_report(
            cfg, plan, pol, mesh_shape, global_batch=8, seq=128,
            mode="train", subject=f"qwen3-14b/{pname}")
        assert rep.ok, rep.format(pname)


def test_lane_warnings_do_not_fail():
    rep = CheckReport()
    from repro.analysis.report import warn
    rep.extend([warn("LAYOUT-LANES", "odd width", "t")])
    assert rep.ok and len(rep.warnings) == 1


def test_axis1_mesh_has_no_comm_payloads():
    """A 1x1 mesh communicates nothing: no payload ever reaches the
    VMEM/layout budgeting (the psum is an identity there)."""
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("qwen3-14b")
    plan = make_plan(cfg, tp=1, fsdp=1)
    pays = commcheck._site_payloads(
        cfg, plan, paper_policy().bind(cfg.n_layers),
        {"data": 1, "model": 1}, global_batch=8, seq=128, n_micro=1,
        mode="train")
    assert pays == []


# ---------------------------------------------------------------------------
# wire-layout partition property (random configs, not just the grid)
# ---------------------------------------------------------------------------


@settings(max_examples=80)
@given(bits=st.integers(min_value=1, max_value=8),
       group=st.sampled_from([32, 64, 128]),
       spike=st.booleans(), scale_int=st.booleans(),
       groups=st.integers(min_value=1, max_value=40))
def test_wire_layout_is_a_partition(bits, group, spike, scale_int, groups):
    cc = CommConfig(bits=bits, group=group, spike=spike,
                    scale_int=scale_int)
    n = groups * group
    lay = cc.wire_layout(n)
    spans = sorted((s.offset, s.end) for _, s in layout._sections(lay))
    cursor = 0
    for off, end in spans:                # exact cover, no overlap
        assert off == cursor and end >= off
        cursor = end
    assert cursor == lay.total == cc.wire_bytes(n)
    assert layout.check_layout(lay, "prop") == []


@settings(max_examples=40, deadline=None)
@given(bits=st.integers(min_value=1, max_value=8),
       group=st.sampled_from([32, 128]),
       spike=st.booleans(), scale_int=st.booleans())
def test_random_config_passes_site_roundtrip(bits, group, spike,
                                             scale_int):
    cc = CommConfig(bits=bits, group=group, spike=spike,
                    scale_int=scale_int)
    assert sites._roundtrip(cc, "prop") == []


# ---------------------------------------------------------------------------
# launch wiring: the fail-fast guard and the CLI
# ---------------------------------------------------------------------------


def _fused_everything():
    pol = with_scheme(paper_policy(), "fused")
    return pol


def test_fused_guard_raises_on_tpu():
    """Full-size fused AR payloads cannot stage in 16 MB VMEM: the
    guard must raise with diagnostics instead of letting pallas_call
    fail minutes into compilation."""
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("qwen3-14b")
    plan = make_plan(cfg, tp=16, fsdp=16)
    with pytest.raises(CommCheckError) as ei:
        commcheck.check_fused_request(
            cfg, plan, _fused_everything(), {"data": 16, "model": 16},
            global_batch=256, seq=4096, n_micro=2, mode="train",
            tpu=True, context="fused-mesh-test")
    fired = ei.value.report.rules_fired()
    assert "SITE-FUSED-MESH" in fired and "VMEM-OVERFLOW" in fired


def test_fused_guard_quiet_off_tpu():
    """Off TPU the fused scheme falls back to XLA emulation — the same
    launch must go through (only the scheme matrix can reject it)."""
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("qwen3-14b")
    plan = make_plan(cfg, tp=16, fsdp=16)
    commcheck.check_fused_request(
        cfg, plan, _fused_everything(), {"data": 16, "model": 16},
        global_batch=256, seq=4096, n_micro=2, mode="train",
        tpu=False, context="fused-mesh-test")


def test_fused_guard_skips_unfused_policies():
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("qwen3-14b")
    plan = make_plan(cfg, tp=16, fsdp=16)
    commcheck.check_fused_request(     # paper policy: no fused site
        cfg, plan, paper_policy(), {"data": 16, "model": 16},
        global_batch=256, seq=4096, n_micro=2, mode="train", tpu=True)


def test_broken_policy_fails_launch_report():
    from repro.configs import get_config
    from repro.parallel.plan import make_plan
    cfg = get_config("moonshot-v1-16b-a3b")
    plan = make_plan(cfg, tp=4, fsdp=2)
    pol = CommPolicy(a2a=CommConfig(bits=4, group=32,
                                    scheme="hierarchical"))
    rep = commcheck.launch_report(cfg, plan, pol,
                                  {"data": 2, "model": 4},
                                  global_batch=8, seq=128, mode="train")
    assert not rep.ok and "SITE-SCHEME" in rep.rules_fired()


def test_cli_rules_and_selftest():
    assert commcheck.main(["--rules"]) == 0
    assert commcheck.main(["--selftest"]) == 0


def test_cli_single_pair():
    assert commcheck.main(["--arch", "qwen3-14b", "--policy", "paper",
                           "--mesh", "2,4"]) == 0


# ---------------------------------------------------------------------------
# the trace lane (one arch; lowering only, no execution)
# ---------------------------------------------------------------------------


def test_trace_lane_qwen3():
    assert sites.trace_train_sites("qwen3-14b", paper_policy()) == []


def test_trace_lane_catches_bypass():
    """A model whose stack never resolves a mandatory site must trip
    SITE-TRACE — simulated by checking the expectation logic directly
    on a recorded log missing the grad site."""
    logged = {("tp", None), ("tp", 0), ("tp_bwd", 0), ("qag", None),
              ("qgrad_rs", None), ("bridge", None)}  # no ("grad", None)
    from repro.core.policy import SITES
    expect = {s for s in SITES if s != "a2a"}
    missing = expect - {s for s, _ in logged}
    assert missing == {"grad"}
