"""Multi-host supervisor: spec round-trips, host dispatch, explicit-index
sharding, remaining-task enumeration, the chaos fault matrix (merged
results bit-identical to a clean unsharded run under every fault class),
and supervisor resume after a mid-sweep death."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.dse import run_dse
from repro.core.explore import (remaining_candidate_indices,
                                sweep_fingerprint)
from repro.dist.faults import FAULT_EXIT_CODE, FaultSpec, plan_faults
from repro.dist.hosts import (LocalProcessHost, ShellCommandHost,
                              parse_hosts)
from repro.dist.supervisor import (Supervisor, SupervisorError, SweepSpec,
                                   quick_spec, read_state,
                                   supervised_results)


def _sig(points):
    return [(p.arch, p.objective, p.energy_j, p.delay_s) for p in points]


def _two_hosts():
    return [LocalProcessHost(name="local0", retry_seed=100),
            LocalProcessHost(name="local1", retry_seed=101)]


@pytest.fixture(scope="module")
def spec():
    return quick_spec(seed=3, n_shards=2)


@pytest.fixture(scope="module")
def clean_sig(spec):
    """The failure-free unsharded run every supervised result must match
    bit-for-bit."""
    pts = run_dse(spec.build_candidates(), spec.build_workloads(),
                  spec.build_cfg(), use_sa=True)
    return _sig(pts)


# ---------------------------------------------------------------------------
# SweepSpec
# ---------------------------------------------------------------------------

def test_spec_json_roundtrip(spec):
    again = SweepSpec.from_json(spec.to_json())
    assert again == spec
    assert again.fingerprint() == spec.fingerprint()
    assert len(spec.build_candidates()) == 6
    assert list(spec.build_workloads()) == ["tf"]


def test_spec_validation():
    with pytest.raises(ValueError):
        SweepSpec(workloads={}, grid={"tops": 72.0})
    with pytest.raises(ValueError):
        SweepSpec(workloads={"tf": "tf-quick"}, grid={"tops": 72.0},
                  n_shards=0)
    with pytest.raises(ValueError):
        SweepSpec(workloads={"tf": "tf-quick"}, grid={"tops": 72.0},
                  screen_keep="auto")
    with pytest.raises(ValueError):
        SweepSpec(workloads={"tf": "tf-quick"}, grid={"tops": 72.0},
                  cfg={"sa": {}})


def test_supervisor_refuses_local_shards_sharing_the_chip(tmp_path):
    spec = quick_spec(n_shards=2)
    spec = SweepSpec.from_dict({**spec.to_dict(), "sa": {
        **spec.sa, "n_chains": 4, "backend": "jax"}})
    with pytest.raises(ValueError, match="one process"):
        Supervisor(spec, tmp_path)


def test_fault_spec_grammar():
    assert FaultSpec.parse("kill") == FaultSpec("kill", 1, 0.0)
    assert FaultSpec.parse("stall:3") == FaultSpec("stall", 3, 0.0)
    assert FaultSpec.parse("slow") == FaultSpec("slow", 1, 0.05)
    s = FaultSpec("corrupt", 2, 0.0)
    assert FaultSpec.parse(s.encode()) == s
    with pytest.raises(ValueError):
        FaultSpec("meteor")


def test_plan_faults_deterministic():
    a = plan_faults(0, 4, "kill")
    assert a == plan_faults(0, 4, "kill")
    (victim,) = a
    assert 0 <= victim < 4
    plans = {tuple(sorted((v, s.k) for v, s in
                          plan_faults(seed, 4, "kill").items()))
             for seed in range(8)}
    assert len(plans) > 1              # the seed actually matters


# ---------------------------------------------------------------------------
# Hosts
# ---------------------------------------------------------------------------

def test_local_process_host_runs_and_logs(tmp_path):
    h = LocalProcessHost()
    log = tmp_path / "out.log"
    handle = h.launch(["-c", "import os; print('env=' + "
                       "os.environ.get('DIST_TEST', ''))"],
                      env={"DIST_TEST": "yes"}, log_path=log)
    assert handle.wait(timeout=30) == 0
    assert "env=yes" in log.read_text()


def test_shell_command_host_loopback(tmp_path):
    """The '{cmd}' template is a local loopback: env prefixes and argv
    quoting must survive the sh -c hop."""
    h = ShellCommandHost("{cmd}", python=sys.executable)
    log = tmp_path / "out.log"
    handle = h.launch(["-c", "import os; print(os.environ['DIST_TEST'])"],
                      env={"DIST_TEST": "a b'c"}, log_path=log)
    assert handle.wait(timeout=30) == 0
    assert "a b'c" in log.read_text()


def test_shell_command_host_requires_cmd_slot():
    with pytest.raises(ValueError, match="cmd"):
        ShellCommandHost("ssh dse-01")


def test_parse_hosts_defaults():
    (h,) = parse_hosts([], 0)
    assert isinstance(h, LocalProcessHost)
    hosts = parse_hosts(["{cmd}"], 2)
    assert len(hosts) == 3
    assert isinstance(hosts[0], ShellCommandHost)


# ---------------------------------------------------------------------------
# Explicit-index sharding + remaining-task enumeration
# ---------------------------------------------------------------------------

def test_indices_run_matches_full_run_slice(spec, clean_sig):
    cands = spec.build_candidates()
    wls = spec.build_workloads()
    cfg = spec.build_cfg()
    pts = run_dse(cands, wls, cfg, use_sa=True, indices=[1, 4],
                  shard_label="sX")
    by_arch = {s[0]: s for s in clean_sig}
    assert sorted(_sig(pts), key=str) == \
        sorted((by_arch[p.arch] for p in pts), key=str)
    assert {p.arch for p in pts} == {cands[1], cands[4]}


def test_indices_validation(spec):
    cands = spec.build_candidates()
    wls = spec.build_workloads()
    cfg = spec.build_cfg()
    with pytest.raises(ValueError, match="stride"):
        run_dse(cands, wls, cfg, indices=[0], shard=(0, 2))
    with pytest.raises(ValueError, match="screen"):
        run_dse(cands, wls, cfg, indices=[0], screen_keep=0.5)
    with pytest.raises(ValueError, match="outside"):
        run_dse(cands, wls, cfg, indices=[99])


def test_remaining_candidate_indices(spec, tmp_path):
    cands = spec.build_candidates()
    wls = spec.build_workloads()
    cfg = spec.build_cfg()
    ckpt = tmp_path / "part.jsonl"
    # no file yet: everything remains
    assert remaining_candidate_indices(cands, wls, cfg, ckpt) == \
        list(range(6))
    run_dse(cands, wls, cfg, use_sa=True, indices=[0, 2, 5],
            checkpoint=ckpt)
    assert remaining_candidate_indices(cands, wls, cfg, ckpt) == [1, 3, 4]
    assert remaining_candidate_indices(cands, wls, cfg, ckpt,
                                       indices=[0, 1, 2]) == [1]
    # a different SA seed invalidates every record (the resume gate)
    cfg2 = quick_spec(seed=4).build_cfg()
    assert remaining_candidate_indices(cands, wls, cfg2, ckpt) == \
        list(range(6))
    with pytest.raises(ValueError, match="outside"):
        remaining_candidate_indices(cands, wls, cfg, ckpt, indices=[77])


def test_sweep_fingerprint_matches_engine(spec, tmp_path):
    wls = spec.build_workloads()
    cfg = spec.build_cfg()
    fp = sweep_fingerprint(wls, cfg)
    ckpt = tmp_path / "c.jsonl"
    run_dse(spec.build_candidates(), wls, cfg, use_sa=True, indices=[0],
            checkpoint=ckpt)
    header = json.loads(ckpt.read_text().splitlines()[0])
    assert header["_config"] == fp


# ---------------------------------------------------------------------------
# Supervisor: happy path, chaos matrix, resume
# ---------------------------------------------------------------------------

def test_supervisor_happy_path_bit_identical(spec, clean_sig, tmp_path):
    sup = Supervisor(spec, out_dir=tmp_path, hosts=_two_hosts(),
                     hb_timeout=60.0, poll_s=0.15)
    merged = sup.run()
    assert _sig(supervised_results(spec, merged)) == clean_sig
    state = read_state(sup.state_path)
    assert state["plan"]["fingerprint"] == spec.fingerprint()
    assert state["merged"] is not None
    evs = [e["ev"] for e in state["events"]]
    assert evs.count("launch") == 2 and "merged" in evs


@pytest.mark.parametrize("kind", ["kill", "corrupt", "dup", "slow",
                                  "stall"])
def test_chaos_matrix_bit_identical(spec, clean_sig, tmp_path, kind):
    """The headline invariant: under every injected fault class the
    supervised sweep's merged result is bit-identical to the clean run."""
    sup = Supervisor(spec, out_dir=tmp_path / kind, hosts=_two_hosts(),
                     hb_timeout=5.0, poll_s=0.15, fault_kind=kind,
                     fault_seed=0)
    merged = sup.run()
    assert _sig(supervised_results(spec, merged)) == clean_sig
    evs = [e["ev"] for e in read_state(sup.state_path)["events"]]
    if kind in ("kill", "corrupt"):
        # the injected crash exits FAULT_EXIT_CODE and must have been
        # retried (or completed post-crash for corrupt)
        rcs = [e["rc"] for e in read_state(sup.state_path)["events"]
               if e["ev"] == "exit"]
        assert FAULT_EXIT_CODE in rcs
    if kind == "stall":
        assert "hb_timeout" in evs and "dead" in evs and "reshard" in evs
    if kind == "dup":
        assert evs.count("launch") >= 3      # the duplicate twin launched


def test_supervisor_resume_after_death(spec, clean_sig, tmp_path):
    """Kill path: one host, one attempt — the victim shard's crash
    exhausts retries, kills the host pool, and the supervisor dies with
    its journal on disk.  A fresh supervisor resumes mid-sweep and
    completes bit-identically."""
    out = tmp_path / "sweep"
    sup = Supervisor(spec, out_dir=out,
                     hosts=[LocalProcessHost(name="only")],
                     hb_timeout=60.0, poll_s=0.15, max_attempts=1,
                     fault_kind="kill", fault_seed=0)
    with pytest.raises(SupervisorError):
        sup.run()
    state = read_state(sup.state_path)
    assert state["merged"] is None
    assert any(e["ev"] == "dead" for e in state["events"])
    sup2 = Supervisor(spec, out_dir=out, hosts=_two_hosts(),
                      hb_timeout=60.0, poll_s=0.15)
    merged = sup2.resume()
    assert _sig(supervised_results(spec, merged)) == clean_sig
    resumed = read_state(sup2.state_path)
    assert any(e["ev"] == "resume" for e in resumed["events"])


def test_supervisor_resume_on_foreign_journal(tmp_path, spec):
    other = quick_spec(seed=99)
    sup = Supervisor(other, out_dir=tmp_path, hosts=_two_hosts())
    sup._event("plan", fingerprint="dse:v2:something-else", keep=[0],
               n_candidates=1, shards=[[0]], spec=other.to_dict())
    sup2 = Supervisor(spec, out_dir=tmp_path, hosts=_two_hosts())
    with pytest.raises(SupervisorError, match="different sweep"):
        sup2.resume()


def test_supervisor_screen_once_matches_sharded_screen(tmp_path):
    """screen_keep < 1: the supervisor screens once and ships the keep
    set; results must match the clean run that screens internally."""
    spec = quick_spec(seed=3, n_shards=2, screen_keep=0.5)
    clean = _sig(run_dse(spec.build_candidates(), spec.build_workloads(),
                         spec.build_cfg(), use_sa=True, screen_keep=0.5))
    sup = Supervisor(spec, out_dir=tmp_path, hosts=_two_hosts(),
                     poll_s=0.15)
    merged = sup.run()
    assert _sig(supervised_results(spec, merged)) == clean
    # only the keep set was dispatched
    plan = read_state(sup.state_path)["plan"]
    assert len(plan["keep"]) == 3


# ---------------------------------------------------------------------------
# sweep_ctl CLI
# ---------------------------------------------------------------------------

def test_sweep_ctl_launch_status_merge(tmp_path, capsys):
    from repro.launch.sweep_ctl import main
    out = tmp_path / "run"
    rc = main(["launch", "--quick", "--out", str(out), "--hosts", "2",
               "--poll", "0.15", "--fault", "kill", "--fault-seed", "0",
               "--verify-clean"])
    assert rc == 0
    assert "bit-identical" in capsys.readouterr().out
    assert main(["status", "--out", str(out)]) == 0
    s = capsys.readouterr().out
    assert "fingerprint" in s and "shard progress" in s
    assert main(["merge", "--out", str(out),
                 "--on-conflict", "error"]) == 0
    assert "complete" in capsys.readouterr().out
