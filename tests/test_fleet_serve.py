"""Fleet spool protocol chaos suite (DESIGN.md §25).

Proves the fleet-serving tentpole guarantees with two daemons sharing
one spool: pickup is an atomic claim (exactly one winner per job),
leases fence stale owners by claim epoch (a host resuming after a GC
pause gets a pinned ``stale_claim``, never a clobbered result), the
reaper sweeps dead hosts' jobs back with attempt counts preserved, the
startup recovery sweep never steals a live peer's work, and affinity
routing prefers warm compile caches without starving any job for more
than one lease period.

The chaos matrix runs ``{hang, sigterm} × {mid-claim, mid-job,
mid-persist, mid-done-rename}`` against in-process daemons (driven
step-by-step for determinism; hang cases run the victim on a thread so
a peer can reclaim mid-pause), plus real-process ``kill`` cases through
``tests/fleet_serve_worker.py``.  Every case asserts the same
invariants: zero jobs lost, exactly one ``job_done`` per job across the
merged per-host ledgers, and results byte-identical to a clean
single-host run.
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from tmlibrary_tpu import faults, resilience, serve, telemetry
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.resilience import EXIT_PREEMPTED
from tmlibrary_tpu.workflow.admission import (
    AdmissionConfig,
    JobSpec,
)
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.engine import (
    WorkflowDescription,
    WorkflowStageDescription,
    WorkflowStepDescription,
)
from tmlibrary_tpu.workflow.registry import register_step

WORKER = Path(__file__).parent / "fleet_serve_worker.py"


@pytest.fixture(autouse=True)
def _clean_state():
    faults.clear()
    resilience.clear_preemption()
    telemetry.reset_registry(enabled=True)
    yield
    faults.clear()
    resilience.clear_preemption()
    telemetry.reset_registry()


# --------------------------------------------------------------- dummy step
@register_step("fleetdummy")
class FleetDummy(Step):
    """Mirror of the step ``fleet_serve_worker.py`` registers: four
    idempotent batches with a launch/persist split so the ``persist``
    fault site is real on the pipelined path."""

    N_BATCHES = 4

    def create_batches(self, args):
        return [{} for _ in range(self.N_BATCHES)]

    def run_batch(self, batch):
        out = self.step_dir / f"out_{batch['index']:03d}.txt"
        out.write_text(f"payload-{batch['index']}")
        return {"i": batch["index"]}

    def launch_batch(self, batch, prefetched=None):
        return batch, {"index": batch["index"]}

    def persist_batch(self, eff, ctx):
        return self.run_batch(eff)


def fleet_description():
    return WorkflowDescription(
        stages=[WorkflowStageDescription(
            name="test", steps=[WorkflowStepDescription(name="fleetdummy")]
        )]
    )


def make_exp(tmp_path, name):
    placeholder = Experiment(
        name=name, plates=[], channels=[], site_height=1, site_width=1
    )
    store = ExperimentStore.create(tmp_path / name, placeholder)
    fleet_description().save(store.workflow_dir / "workflow.yaml")
    return store


def spec(job_id, root, tenant="a", **kw):
    kw.setdefault("submitted_at", 1000.0)
    return JobSpec(job_id=job_id, root=str(root), tenant=tenant, **kw)


def outputs(store):
    step_dir = store.workflow_dir / "fleetdummy"
    return {p.name: p.read_text() for p in step_dir.glob("out_*.txt")}


#: what a clean single-host run leaves behind — FleetDummy is
#: deterministic, so byte-identity to a clean run is identity to this
CLEAN_OUTPUTS = {f"out_{i:03d}.txt": f"payload-{i}" for i in range(4)}


def daemon(sroot, host, lease=0.15):
    return serve.ServeDaemon(
        sroot, admission=AdmissionConfig(max_queue=32, tenant_quota=32),
        poll_s=0.01, install_handlers=False, host=host, lease_s=lease,
    )


def execute_all(d):
    """Drain one daemon's admitted queue to outcomes (the run() loop's
    execute half, without the wall-clock poll)."""
    outcomes = {}
    while True:
        job = d.queue.take()
        if job is None:
            return outcomes
        outcomes[job.job_id] = d._execute(job)


def merged(sroot):
    return serve.serve_ledger_events(sroot)


def assert_exactly_once(sroot, stores, job_ids):
    """The chaos-matrix invariants: no job lost, one ``job_done`` per
    job across the merged per-host ledgers, spool fully drained (no
    leftover claims), and per-store outputs byte-identical to a clean
    single-host run."""
    events = merged(sroot)
    done = sorted(e["job"] for e in events if e.get("event") == "job_done")
    assert done == sorted(job_ids), f"job_done events: {done}"
    for state in ("incoming", "admitted"):
        assert not list(serve.spool_dir(sroot, state).glob("*.json"))
    assert not serve.job_claims(sroot)
    assert (sorted(p.stem for p in
                   serve.spool_dir(sroot, "done").glob("*.json"))
            == sorted(job_ids))
    for store in stores:
        assert outputs(store) == CLEAN_OUTPUTS


def expire_lease(sroot, job_id, host):
    """Rewrite one claim's lease deadline into the past and erase the
    owner's heartbeat freshness — the on-disk signature of a dead host,
    without waiting out a real lease."""
    cpath = serve.claim_path(sroot, job_id, host)
    claim = json.loads(cpath.read_text())
    claim["lease_deadline"] = time.time() - 60.0
    claim["claimed_at"] = time.time() - 120.0
    cpath.write_text(json.dumps(claim))
    old = time.time() - 3600.0
    os.utime(cpath, (old, old))
    hb = serve.heartbeat_file(sroot, host)
    if hb.exists():
        data = json.loads(hb.read_text())
        data["ts"] = old
        hb.write_text(json.dumps(data))
        os.utime(hb, (old, old))


# ======================================================== claim arbitration
def test_concurrent_scans_claim_each_job_exactly_once(tmp_path):
    """Two daemons scanning one spool concurrently: the atomic claim
    rename guarantees exactly one winner per job, the union covers
    every job, and both daemons' executions land all jobs done with
    clean-run bytes."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(6)]
    jobs = []
    for i, store in enumerate(stores):
        serve.enqueue_job(sroot, spec(f"a-{i}", store.root))
        jobs.append(f"a-{i}")
    d1, d2 = daemon(sroot, "h1", lease=5.0), daemon(sroot, "h2", lease=5.0)

    threads = [threading.Thread(target=d._scan_incoming)
               for d in (d1, d2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    with d1._claims_lock:
        c1 = set(d1._claims)
    with d2._claims_lock:
        c2 = set(d2._claims)
    assert not (c1 & c2), "both daemons claimed the same job"
    assert c1 | c2 == set(jobs)
    # one job_admitted per job across the merged ledgers, each epoch 1
    admitted = [e for e in merged(sroot) if e.get("event") == "job_admitted"]
    assert sorted(e["job"] for e in admitted) == jobs
    assert all(e["epoch"] == 1 for e in admitted)

    execute_all(d1)
    execute_all(d2)
    assert_exactly_once(sroot, stores, jobs)


def test_duplicate_submission_rejected_only_while_lease_live(tmp_path):
    """An incoming spec whose job id is admitted under a *live* lease is
    a duplicate; the same spec against a claim-less admitted residue
    (torn reclaim) must be claimable instead of wedging forever."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-1", store.root))
    d1, d2 = daemon(sroot, "h1", lease=5.0), daemon(sroot, "h2", lease=5.0)
    d1._scan_incoming()  # h1 holds the lease

    # duplicate while live: rejected with the pinned duplicate reason
    serve.enqueue_job(sroot, spec("a-1", store.root))
    d2._scan_incoming()
    rej = [e for e in merged(sroot) if e.get("event") == "job_rejected"]
    assert [e["reason"] for e in rej] == ["duplicate"]
    assert not list(serve.spool_dir(sroot, "incoming").glob("*.json"))

    # torn-reclaim residue: admitted spec present but claim file gone —
    # the SAME id re-submitted must be claimed, not rejected
    serve.claim_path(sroot, "a-1", "h1").unlink()
    with d1._claims_lock:
        d1._claims.clear()
    serve.enqueue_job(sroot, spec("a-1", store.root))
    d2._scan_incoming()
    assert execute_all(d2) == {"a-1": "done"}
    assert outputs(store) == CLEAN_OUTPUTS


# ================================================================== reaper
def test_reaper_reclaims_dead_host_jobs_preserving_attempts(tmp_path):
    """A dead host's leases (deadline passed + heartbeat stale) are
    swept back to incoming/ with attempt counts and epochs preserved,
    sealed as ``job_reclaimed``, and the survivor completes every job
    exactly once."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(2)]
    serve.enqueue_job(sroot, spec("a-0", stores[0].root))
    serve.enqueue_job(sroot, spec("a-1", stores[1].root, attempt=2))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()  # h1 claims both, then "dies" (never executes)
    for jid in ("a-0", "a-1"):
        expire_lease(sroot, jid, "h1")

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._reap_expired() == 2
    reclaimed = [e for e in merged(sroot)
                 if e.get("event") == "job_reclaimed"]
    assert sorted(e["job"] for e in reclaimed) == ["a-0", "a-1"]
    assert all(e["from_host"] == "h1" and e["epoch"] == 1
               for e in reclaimed)
    assert {e["job"]: e["attempt"] for e in reclaimed} == \
        {"a-0": 0, "a-1": 2}
    # re-spooled specs carry epoch + attempt forward
    respooled = json.loads(
        (serve.spool_dir(sroot, "incoming") / "a-1.json").read_text())
    assert respooled["claim_epoch"] == 1 and respooled["attempt"] == 2

    d2._scan_incoming()
    execute_all(d2)
    assert_exactly_once(sroot, stores, ["a-0", "a-1"])
    # the survivor re-claimed at a higher epoch
    admitted = [e for e in merged(sroot)
                if e.get("event") == "job_admitted" and e.get("epoch") == 2]
    assert sorted(e["job"] for e in admitted) == ["a-0", "a-1"]


def test_reaper_spares_live_host_with_wedged_renewal(tmp_path):
    """An expired lease whose owner still heartbeats is NOT reclaimed —
    one missed renewal (wedged thread) must not cause a double run."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    # deadline in the past, but the heartbeat stays fresh
    cpath = serve.claim_path(sroot, "a-0", "h1")
    claim = json.loads(cpath.read_text())
    claim["lease_deadline"] = time.time() - 60.0
    cpath.write_text(json.dumps(claim))
    d1._write_serve_heartbeat(queue_depth=0)

    d2 = daemon(sroot, "h2")
    assert d2._reap_expired() == 0
    assert (serve.spool_dir(sroot, "admitted") / "a-0.json").exists()
    assert execute_all(d1) == {"a-0": "done"}


def test_lease_renewal_extends_deadline_and_faults_are_counted(tmp_path):
    """The renewal pass pushes every held lease's deadline forward and
    refreshes the per-host heartbeat; a LeaseRenewer survives injected
    renewal faults (counted, not raised)."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    cpath = serve.claim_path(sroot, "a-0", "h1")
    before = json.loads(cpath.read_text())["lease_deadline"]
    time.sleep(0.02)
    d1._renew_leases()
    after = json.loads(cpath.read_text())
    assert after["lease_deadline"] > before and after["epoch"] == 1
    assert serve.heartbeat_file(sroot, "h1").exists()

    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="lease_renew", kind="io_error", step="h1"),
    ]))
    renewer = resilience.LeaseRenewer(d1._renew_leases, period=60.0)
    assert renewer.renew_now() is False and renewer.failures == 1
    faults.clear()
    assert renewer.renew_now() is True
    execute_all(d1)


# ================================================= startup recovery (race)
def test_recovery_sweep_spares_live_peer_claims(tmp_path):
    """Satellite regression: a restarting daemon's recovery sweep must
    NOT steal a job whose claim belongs to a live peer (the seed swept
    admitted/ unconditionally — two daemons meant double execution),
    while dead/our-own/claim-less leftovers still recover."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(3)]
    for i, store in enumerate(stores):
        serve.enqueue_job(sroot, spec(f"a-{i}", store.root))
    d1 = daemon(sroot, "h1", lease=5.0)
    d1._scan_incoming()  # h1 claims all three, stays alive
    d1._write_serve_heartbeat(queue_depth=3)

    # a-1's lease expires with the owner dead; a-2 loses its claim file
    # entirely (torn claim)
    expire_lease(sroot, "a-1", "h1")
    serve.claim_path(sroot, "a-2", "h1").unlink()

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._recover_spool() == 2
    requeued = sorted(
        e["job"] for e in merged(sroot)
        if e.get("event") == "job_requeued"
        and e.get("phase") == "recovery")
    assert requeued == ["a-1", "a-2"]
    # the live peer's job was untouched
    assert (serve.spool_dir(sroot, "admitted") / "a-0.json").exists()
    assert serve.claim_path(sroot, "a-0", "h1").exists()

    # NOTE expire_lease backdated h1's heartbeat, so re-freshen for a-0
    d1._write_serve_heartbeat(queue_depth=3)
    d2._scan_incoming()
    execute_all(d2)
    assert execute_all(d1) == {"a-0": "done", "a-1": "stale",
                               "a-2": "stale"}
    assert_exactly_once(sroot, stores, ["a-0", "a-1", "a-2"])


# ==================================================== epoch fencing (both)
def test_stale_owner_fenced_after_reclaimed_job_completes(tmp_path):
    """Ordering 1: the reclaimed job's second execution wins first; the
    paused first owner then attempts its ``done`` rename and gets a
    pinned ``stale_claim`` — the winner's result is never clobbered."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    expire_lease(sroot, "a-0", "h1")  # h1 pauses; lease lapses

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._reap_expired() == 1
    d2._scan_incoming()
    assert execute_all(d2) == {"a-0": "done"}
    done_path = serve.spool_dir(sroot, "done") / "a-0.json"
    winner_bytes = done_path.read_bytes()

    # h1 wakes up and runs its stale copy to completion
    assert execute_all(d1) == {"a-0": "stale"}
    assert done_path.read_bytes() == winner_bytes
    events = merged(sroot)
    assert [e["job"] for e in events if e.get("event") == "job_done"] \
        == ["a-0"]
    stale = [e for e in events if e.get("event") == "stale_claim"]
    assert len(stale) == 1 and stale[0]["epoch"] == 1
    assert stale[0]["outcome"] == "done"
    assert telemetry.get_registry().counter(
        "tmx_serve_stale_claims_total", tenant="a", host="h1").value == 1
    assert_exactly_once(sroot, [store], ["a-0"])


def test_stale_owner_fenced_before_reclaimed_job_reruns(tmp_path):
    """Ordering 2: the paused owner attempts its ``done`` rename
    *before* the reclaimed job re-runs — fenced, nothing lands in
    done/, and the second execution then completes exactly once."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    expire_lease(sroot, "a-0", "h1")

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._reap_expired() == 1  # re-spooled, NOT yet re-run

    # stale owner finishes first: fenced, no done/ entry
    assert execute_all(d1) == {"a-0": "stale"}
    assert not (serve.spool_dir(sroot, "done") / "a-0.json").exists()
    assert (serve.spool_dir(sroot, "incoming") / "a-0.json").exists()

    d2._scan_incoming()
    assert execute_all(d2) == {"a-0": "done"}
    assert_exactly_once(sroot, [store], ["a-0"])
    events = merged(sroot)
    assert len([e for e in events if e.get("event") == "stale_claim"]) == 1


# ============================================================ chaos matrix
def _drive_until_preempted(d):
    """The run() loop's scan/execute half under a SIGTERM chaos kind:
    drive until the preemption flag stops the loop, then drain exactly
    as run() would."""
    current = None
    d._scan_incoming()
    while not resilience.preemption_requested():
        job = d.queue.take()
        if job is None:
            break
        outcome = d._execute(job)
        if outcome == "preempted":
            current = job
            break
    if resilience.preemption_requested():
        assert d._drain_and_exit(current=current) == EXIT_PREEMPTED
    resilience.clear_preemption()


@pytest.mark.parametrize("site", ["claim", "batch_run", "persist",
                                  "done_rename"])
def test_fleet_chaos_sigterm(tmp_path, site):
    """SIGTERM × {mid-claim, mid-job, mid-persist, mid-done-rename}:
    the victim drains (claims released, epochs preserved) and the
    survivor finishes every job exactly once with clean-run bytes."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(2)]
    jobs = []
    for i, store in enumerate(stores):
        serve.enqueue_job(
            sroot, spec(f"a-{i}", store.root, pipeline_depth=2))
        jobs.append(f"a-{i}")
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site=site, kind="sigterm"),
    ]))
    restore = resilience.install_preemption_handlers()
    try:
        d1 = daemon(sroot, "h1", lease=5.0)
        _drive_until_preempted(d1)
    finally:
        restore()
        resilience.clear_preemption()
    faults.clear()

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._recover_spool() == 0  # drain left nothing under lease
    d2._scan_incoming()
    execute_all(d2)
    execute_all(d1)  # anything the victim still held pre-drain
    assert_exactly_once(sroot, stores, jobs)


def test_fleet_chaos_hang_mid_claim(tmp_path):
    """hang × mid-claim: the victim stalls between winning the claim
    rename and writing the lease — the admitted spec is orphaned
    claim-less, and the peer's orphan pass reclaims it."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(2)]
    for i, store in enumerate(stores):
        serve.enqueue_job(sroot, spec(f"a-{i}", store.root))
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="claim", kind="hang", seconds=0.2),
    ]))
    d1 = daemon(sroot, "h1", lease=0.1)
    d1._scan_incoming()  # first claim hangs 0.2s then faults; second ok
    faults.clear()
    with d1._claims_lock:
        assert len(d1._claims) == 1  # the orphaned job was NOT claimed
    orphans = [f for f in
               serve.spool_dir(sroot, "admitted").glob("*.json")
               if not serve.job_claims(sroot, f.stem)]
    assert len(orphans) == 1
    # age the orphan past the reaper's one-lease-period grace
    old = time.time() - 60.0
    os.utime(orphans[0], (old, old))

    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._reap_expired() == 1  # grace elapsed
    d2._scan_incoming()
    execute_all(d2)
    execute_all(d1)
    assert_exactly_once(sroot, stores, ["a-0", "a-1"])


@pytest.mark.parametrize("site", ["batch_run", "persist", "done_rename"])
def test_fleet_chaos_hang_is_fenced_after_reclaim(tmp_path, site):
    """hang × {mid-job, mid-persist, mid-done-rename}: the victim
    pauses past its lease mid-execution (the GC-pause scenario), a peer
    reclaims and completes the job, and the victim's late terminal
    transition is fenced — exactly one ``job_done``, winner's bytes."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root, pipeline_depth=2))
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site=site, kind="hang", seconds=1.2),
    ]))
    d1 = daemon(sroot, "h1", lease=0.15)
    d1._scan_incoming()
    outcomes = {}
    t = threading.Thread(
        target=lambda: outcomes.update(victim=execute_all(d1)))
    t.start()
    deadline = time.time() + 5.0
    d2 = daemon(sroot, "h2", lease=5.0)
    while time.time() < deadline:  # wait out the victim's lease
        time.sleep(0.05)
        if d2._reap_expired():
            break
    else:
        pytest.fail("reaper never reclaimed the paused victim's job")
    faults.clear()  # the survivor must run fault-free
    d2._scan_incoming()
    assert execute_all(d2) == {"a-0": "done"}
    winner_bytes = (serve.spool_dir(sroot, "done") / "a-0.json").read_bytes()
    t.join(timeout=10.0)
    assert not t.is_alive()
    # whatever the victim's engine did after waking, it never published
    assert outcomes["victim"].get("a-0") in ("stale", "failed")
    assert (serve.spool_dir(sroot, "done") / "a-0.json").read_bytes() \
        == winner_bytes
    events = merged(sroot)
    assert [e["job"] for e in events if e.get("event") == "job_done"] \
        == ["a-0"]
    assert [e for e in events if e.get("event") == "stale_claim"]
    assert_exactly_once(sroot, [store], ["a-0"])


@pytest.mark.parametrize("site", ["claim", "batch_run"])
def test_fleet_chaos_kill_subprocess_reclaim(tmp_path, site):
    """kill × {mid-claim, mid-job} in a REAL process: the daemon
    hard-exits (os._exit(41)) at the armed site, the surviving host
    reclaims its leases and finishes every job exactly once with
    clean-run bytes — the full dead-host story, no simulation."""
    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(2)]
    jobs = []
    for i, store in enumerate(stores):
        serve.enqueue_job(sroot, spec(f"a-{i}", store.root))
        jobs.append(f"a-{i}")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["TMX_FAULT_PLAN"] = json.dumps(
        {"faults": [{"site": site, "kind": "kill"}]})
    proc = subprocess.run(
        [sys.executable, str(WORKER), str(sroot), "hA", "0.3", "0", "10"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 41, \
        f"worker should die at the injected kill:\n{proc.stderr[-2000:]}"

    time.sleep(0.35)  # let the dead host's lease lapse
    d2 = daemon(sroot, "h2", lease=5.0)
    d2._recover_spool()
    d2._reap_expired()
    d2._scan_incoming()
    execute_all(d2)
    assert_exactly_once(sroot, stores, jobs)
    # merged per-host ledgers tell one coherent story: hA's events and
    # h2's completions, with no job finishing twice
    hosts = {e.get("host") for e in merged(sroot) if e.get("host")}
    assert "h2" in hosts


# ======================================================== affinity routing
def test_affinity_routing_prefers_warm_host_with_staleness_bound(tmp_path):
    """Cold-key jobs are deferred to affine live peers (affinity=miss
    never happens while a warm host exists), but never wait longer than
    one lease period; hits/misses land on the admitted events and the
    hit counter replays from the merged ledger."""
    sroot = tmp_path / "srv"
    s1 = make_exp(tmp_path, "exp1")
    s2 = make_exp(tmp_path, "exp2")
    # distinct pipeline content => distinct affinity keys
    (s2.root / "extra.pipe.yaml").write_text("pipeline: [x]\n")
    j1 = spec("a-1", s1.root, submitted_at=time.time())
    j2 = spec("a-2", s2.root, submitted_at=time.time())
    serve.enqueue_job(sroot, j1)
    serve.enqueue_job(sroot, j2)
    k1, k2 = j1.affinity_key, j2.affinity_key
    assert k1 and k2 and k1 != k2

    d1, d2 = daemon(sroot, "h1", lease=0.5), daemon(sroot, "h2", lease=0.5)
    d1._warm_keys.add(k1)
    d2._warm_keys.add(k2)
    d1._write_serve_heartbeat(queue_depth=0)
    d2._write_serve_heartbeat(queue_depth=0)

    d2._scan_incoming()  # defers cold j1, claims warm j2
    with d2._claims_lock:
        assert set(d2._claims) == {"a-2"}
    assert (serve.spool_dir(sroot, "incoming") / "a-1.json").exists()
    d1._scan_incoming()  # claims its warm j1
    with d1._claims_lock:
        assert set(d1._claims) == {"a-1"}
    admitted = {e["job"]: e for e in merged(sroot)
                if e.get("event") == "job_admitted"}
    assert admitted["a-1"]["affinity"] == "hit"
    assert admitted["a-2"]["affinity"] == "hit"

    # staleness bound: a cold-key job older than one lease period is
    # claimed by ANY host, as a miss
    j3 = spec("a-3", s1.root, submitted_at=time.time() - 10.0)
    serve.enqueue_job(sroot, j3)
    d2._scan_incoming()
    with d2._claims_lock:
        assert "a-3" in d2._claims
    admitted = {e["job"]: e for e in merged(sroot)
                if e.get("event") == "job_admitted"}
    assert admitted["a-3"]["affinity"] == "miss"

    execute_all(d1)
    execute_all(d2)
    # live counter and ledger replay agree (per-host labels)
    assert telemetry.get_registry().counter(
        "tmx_serve_affinity_hits_total", tenant="a", host="h1").value == 1
    reg = telemetry.registry_from_ledger(merged(sroot))
    assert reg.counter("tmx_serve_affinity_hits_total",
                       tenant="a", host="h1").value == 1
    assert reg.counter("tmx_serve_affinity_hits_total",
                       tenant="a", host="h2").value == 1


def test_cold_host_with_no_warm_keys_claims_everything(tmp_path):
    """A freshly started host has no preference basis: it must claim
    cold-key jobs immediately (no deferral deadlock on a quiet fleet)."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(
        sroot, spec("a-0", store.root, submitted_at=time.time()))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    assert execute_all(d1) == {"a-0": "done"}


# ==================================== merged-ledger replay + status surface
def test_fleet_status_view_replay_parity_and_top_row(tmp_path, capsys):
    """Satellite: the fleet view — per-host heartbeat/lease rows,
    reclaim + stale-claim + affinity totals — on `tmx serve status
    --json`, the FLEET row in `tmx top`, and metric parity between the
    live registry and registry_from_ledger over the merged history."""
    from tmlibrary_tpu.cli import main

    sroot = tmp_path / "srv"
    stores = [make_exp(tmp_path, f"exp{i}") for i in range(2)]
    serve.enqueue_job(sroot, spec("a-0", stores[0].root))
    serve.enqueue_job(sroot, spec("a-1", stores[1].root))
    d1 = daemon(sroot, "h1")
    d1._scan_incoming()
    d1._write_serve_heartbeat(queue_depth=2)
    expire_lease(sroot, "a-0", "h1")  # also backdates h1's heartbeat
    expire_lease(sroot, "a-1", "h1")
    d2 = daemon(sroot, "h2", lease=5.0)
    assert d2._reap_expired() == 2
    d2._scan_incoming()
    execute_all(d2)
    assert execute_all(d1) == {"a-0": "stale", "a-1": "stale"}
    d2._write_serve_heartbeat(queue_depth=0)
    d2._publish_state()

    view = serve.serve_status_view(sroot)
    fleet = view["fleet"]
    assert fleet["reclaims_total"] == 2
    assert fleet["stale_claims_total"] == 2
    assert "h2" in fleet["hosts"] and fleet["hosts"]["h2"]["live"]
    assert "h1" in fleet["hosts"] and not fleet["hosts"]["h1"]["live"]
    assert sorted(fleet["ledgers"]) == ["ledger.h1.jsonl",
                                        "ledger.h2.jsonl"]
    assert view["tenants"]["a"]["reclaimed"] == 2
    assert view["tenants"]["a"]["done"] == 2

    # live registry vs merged-ledger replay: the serve counters agree
    live = telemetry.get_registry()
    replay = telemetry.registry_from_ledger(merged(sroot))
    for name, labels in (
        ("tmx_serve_reclaims_total", {"tenant": "a", "host": "h2"}),
        ("tmx_serve_stale_claims_total", {"tenant": "a", "host": "h1"}),
        ("tmx_serve_jobs_done_total", {"tenant": "a", "host": "h2"}),
        ("tmx_serve_admitted_total", {"tenant": "a", "host": "h2"}),
    ):
        assert (replay.counter(name, **labels).value
                == live.counter(name, **labels).value != 0), name

    assert main(["serve", "status", "--root", str(sroot), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fleet"]["reclaims_total"] == 2
    assert main(["serve", "status", "--root", str(sroot)]) == 0
    text = capsys.readouterr().out
    assert "fleet: 2 host(s)" in text and "reclaims 2" in text

    assert main(["top", "--root", str(sroot), "--once", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["serve"]["fleet"]["stale_claims_total"] == 2
    assert main(["top", "--root", str(sroot), "--once"]) == 0
    top_text = capsys.readouterr().out
    assert "fleet" in top_text and "reclaims 2" in top_text

    # `tmx slo` reads the merged per-host ledgers (no legacy
    # ledger.jsonl exists in this fleet)
    assert main(["slo", "--root", str(sroot)]) == 0
    assert "tenant a" in capsys.readouterr().out


def test_shed_decisions_replay_identically_from_merged_ledgers(tmp_path):
    """Overload shedding on a fleet member derives from the merged
    history exactly as the live registry recorded it — admission/shed
    decisions stay pure functions of the ledger."""
    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    for i in range(5):
        serve.enqueue_job(sroot, spec(f"a-{i}", store.root))
    d1 = serve.ServeDaemon(
        sroot, admission=AdmissionConfig(max_queue=2, low_watermark=1,
                                         tenant_quota=32),
        poll_s=0.01, install_handlers=False, host="h1", lease_s=5.0)
    d1._scan_incoming()  # 2 admitted, 3 shed

    live = telemetry.get_registry()
    replay = telemetry.registry_from_ledger(merged(sroot))
    for name, labels in (
        ("tmx_serve_shed_total", {"tenant": "a", "host": "h1"}),
        ("tmx_serve_admitted_total", {"tenant": "a", "host": "h1"}),
        ("tmx_serve_rejected_total", {"tenant": "a", "host": "h1",
                                      "reason": "queue_full"}),
    ):
        assert (replay.counter(name, **labels).value
                == live.counter(name, **labels).value != 0), name
    execute_all(d1)


# ================================== a claim's temp file is no claim (PR 36)
@pytest.mark.parametrize("style", ["thread_temp_name", "pid_temp_name"])
def test_reaper_takes_no_temp_file_of_a_claim_for_a_claim(tmp_path, style):
    """PERF.md section 7's first race, as its deterministic witness: while
    the lease renewer rewrites ``<job>.claim.<host>`` its temp file lies
    beside it; ``job_claims`` read it as a claim of a host named
    ``<host>.<pid>.tmp``, unreadable, and the daemon's own reaper swept
    the live job back to ``incoming/`` (one re-spool).  None now, under
    the temp name ``atomicio`` gives a writer thread and under the one it
    gave a process before."""
    from tmlibrary_tpu import atomicio

    sroot = tmp_path / "srv"
    store = make_exp(tmp_path, "exp")
    serve.enqueue_job(sroot, spec("a-0", store.root))
    d1 = daemon(sroot, "h1", lease=15.0)
    d1._scan_incoming()
    cpath = serve.claim_path(sroot, "a-0", "h1")
    assert cpath.exists()
    temp = (atomicio.temp_path(cpath) if style == "thread_temp_name"
            else cpath.with_name(f"{cpath.name}.{os.getpid()}.tmp"))
    temp.write_text('{"job_id": "a-0", "lease_dead')   # half a claim
    assert temp.name.endswith(atomicio.TMP_SUFFIX)
    assert [c[2] for c in serve.job_claims(sroot)] == ["h1"]
    assert d1._reap_expired() == 0
    assert not [e for e in merged(sroot)
                if e.get("event") == "job_reclaimed"]
    assert (serve.spool_dir(sroot, "admitted") / "a-0.json").exists()
    temp.unlink()
    assert execute_all(d1) == {"a-0": "done"}
