"""Job-store kill -9 soak (`make soak-jobstore`, ISSUE 19): SIGKILL a
REAL process mid-transition — claimed leases in flight, terminal
verdicts streaming — and recover a fresh JobStore over the same tier
directory.

The claims under test, end to end across a process boundary:

  * **zero lost** — every mutation the child ACKED (the ack line prints
    only after the store call returned, i.e. after the WAL append) is
    present after recovery with the acked status;
  * **zero double-scored** — acked terminal verdicts stay terminal: the
    recovered store will not lease them again, and their verdicts are
    untouched;
  * **provenance chain intact** — the spilled provenance record for
    every acked terminal verdict survives with its hop chain;
  * **replay-twice == replay-once** — re-replaying the same WAL is pure
    counted stale no-ops and changes no verdict byte;
  * **disk chaos degrades, never corrupts** — with `disk=PROB:kind`
    faults at the WAL/segment append seams the child keeps acking
    (counted degradation), and recovery over the damaged directory is
    still clean and self-consistent.

Marked slow+chaos so tier-1 (-m 'not slow') stays fast.
"""
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from foremast_tpu.engine import jobs as J
from foremast_tpu.engine.jobs import JobStore, verdict_digest
from foremast_tpu.engine.jobtier import JobTier

pytestmark = [pytest.mark.slow, pytest.mark.chaos]


@pytest.fixture(autouse=True)
def _debug_locks(monkeypatch):
    """Soak under the lock-order tracer (FOREMAST_DEBUG_LOCKS=1), same
    gate as the chaos soak: recovery + replay over the kill -9 debris
    must also never exhibit a held-before cycle. The env var propagates
    to the SIGKILLed child too (subprocess inherits os.environ), so the
    parent-side assertion covers the recovery half and the child runs
    with traced locks for free."""
    from foremast_tpu.devtools.locktrace import tracer

    monkeypatch.setenv("FOREMAST_DEBUG_LOCKS", "1")
    tracer.reset()
    yield
    rep = tracer.report()
    assert not rep["cycles"], rep["cycles"]


_CHILD = textwrap.dedent("""
    import os, sys
    from foremast_tpu.engine import jobs as J
    from foremast_tpu.engine.jobs import Document, JobStore
    from foremast_tpu.engine.jobtier import JobTier
    from foremast_tpu.resilience.faults import FaultInjector, \\
        parse_chaos_spec

    store_dir, chaos = sys.argv[1], sys.argv[2]
    injector = None
    if chaos:
        seed, plans = parse_chaos_spec(chaos)
        if "disk" in plans:
            injector = FaultInjector(plans["disk"], seed=seed,
                                     target="disk")
    tier = JobTier(store_dir, injector=injector)
    store = JobStore(tier=tier, tier_hot_seconds=0.0,
                     tier_checkpoint_min_seconds=0.0)

    def ack(line):
        # the line prints ONLY after the mutating call returned — it is
        # the ack the parent holds the store to after the kill
        sys.stdout.write(line + "\\n")
        sys.stdout.flush()

    i = 0
    while True:  # runs until SIGKILL
        jid = f"soak-{i:05d}"
        store.create(Document(id=jid, app_name=f"app-{i % 11}",
                              strategy="canary", start_time="0",
                              end_time="0"))
        ack(f"CREATE {jid}")
        claimed = store.claim_open_jobs(f"w{i % 3}", limit=1,
                                        only_ids={jid})
        if claimed:
            ack(f"CLAIM {jid} w{i % 3}")
        # score all but every 7th job (those stay claimed-in-flight, so
        # a kill at ANY moment leaves open leases behind)
        if i % 7 != 6 and claimed:
            store.advance(jid, J.PREPROCESS_COMPLETED,
                          J.POSTPROCESS_INPROGRESS)
            verdict = (J.COMPLETED_UNHEALTH if i % 5 == 0
                       else J.COMPLETED_HEALTH)
            # the recorder's spill hook runs before the verdict acks:
            # the chain must be readable the instant the verdict is
            tier.spill_prov(jid, {"job_id": jid, "verdict": verdict,
                                  "hops": [{"worker": f"w{i % 3}",
                                            "action": "scored"}]})
            store.transition(jid, verdict, reason=f"scored #{i}")
            ack(f"TERM {jid} {verdict}")
        if i % 50 == 49:
            store.tier_checkpoint(force=True)
            ack(f"CKPT {i}")
        i += 1
""")


def _spawn(tmp_path, store_dir, chaos=""):
    script = tmp_path / "soaker.py"
    if not script.exists():
        script.write_text(_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (repo_root, os.environ.get("PYTHONPATH"))
                   if p))
    env.pop("FOREMAST_CHAOS", None)
    return subprocess.Popen(
        [sys.executable, str(script), store_dir, chaos],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)


def _run_until_kill(proc, min_acks: int, budget_s: float = 60.0):
    """Read ack lines until at least `min_acks` landed AND the child is
    mid-stream (a checkpoint has happened), then SIGKILL. Returns the
    complete acked lines — a torn final line (no newline) is NOT an ack
    and is dropped."""
    acks = []
    deadline = time.monotonic() + budget_s
    saw_ckpt = False
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if not line.endswith(b"\n"):
            break  # torn write at the pipe: never acked
        text = line.decode().strip()
        acks.append(text)
        saw_ckpt = saw_ckpt or text.startswith("CKPT")
        if len(acks) >= min_acks and saw_ckpt:
            break
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(10)
    # drain whatever was already buffered in the pipe — every complete
    # line was acked before the kill
    rest = proc.stdout.read() or b""
    for line in rest.split(b"\n")[:-1]:
        acks.append(line.decode().strip())
    assert len(acks) >= min_acks, f"only {len(acks)} acks before budget"
    return acks


def _preserve(store_dir, name):
    """Freeze the crashed WAL+segment directory where CI's on-failure
    artifact upload can find it (ci.yml soak job uploads
    /tmp/foremast-jobstore-dumps/ next to the flight dumps), so a red
    soak is diagnosable from the Actions UI alone."""
    try:
        dst = os.path.join("/tmp/foremast-jobstore-dumps", name)
        shutil.rmtree(dst, ignore_errors=True)
        shutil.copytree(store_dir, dst)
    except OSError:
        pass


def _parse_acks(acks):
    created, claimed, terms = set(), {}, {}
    for line in acks:
        parts = line.split()
        if parts[0] == "CREATE":
            created.add(parts[1])
        elif parts[0] == "CLAIM":
            claimed[parts[1]] = parts[2]
        elif parts[0] == "TERM":
            terms[parts[1]] = parts[2]
    return created, claimed, terms


def _recover(store_dir):
    store = JobStore(tier=JobTier(store_dir), tier_hot_seconds=0.0,
                     tier_checkpoint_min_seconds=0.0)
    stats = store.recover_from_tier()
    return store, stats


def test_jobstore_soak_kill9_zero_lost_zero_double_scored(tmp_path):
    store_dir = str(tmp_path / "jobstore")
    proc = _spawn(tmp_path, store_dir)
    try:
        acks = _run_until_kill(proc, min_acks=400)
    finally:
        proc.kill()
    created, claimed, terms = _parse_acks(acks)
    assert created and terms, "soak produced no work"
    open_claimed = {j: w for j, w in claimed.items() if j not in terms}
    assert open_claimed, "kill left no claimed leases in flight"

    # freeze the crashed directory for the replay-twice leg BEFORE the
    # first recovery retires the WAL
    replay_dir = str(tmp_path / "jobstore-replay")
    shutil.copytree(store_dir, replay_dir)
    _preserve(store_dir, "kill9")

    store, stats = _recover(store_dir)
    assert stats["wal_records_replayed"] > 0 or stats["segment_docs"] > 0

    # ZERO LOST: every acked mutation is present with its acked state
    for jid in created:
        doc = store.get(jid)
        assert doc is not None, f"acked create lost: {jid}"
    for jid, verdict in terms.items():
        doc = store.get(jid)
        assert doc.status == verdict, \
            f"acked verdict lost: {jid} {doc.status} != {verdict}"
        assert doc.reason.startswith("scored #")
    # claimed-in-flight jobs recovered OPEN with their lease intact.
    # At most ONE may instead be terminal: the job mid-flight at the
    # kill, whose verdict was WAL'd but whose TERM ack died in the pipe
    # (durable-but-unacked is a legal superset, never a loss).
    still_open = 0
    for jid, worker in open_claimed.items():
        doc = store.get(jid)
        if doc.status in J.TERMINAL_STATUSES:
            continue
        assert doc.status in J.OPEN_STATUSES, (jid, doc.status)
        assert doc.lease_holder == worker, (jid, doc.lease_holder)
        still_open += 1
    assert still_open >= len(open_claimed) - 1

    # ZERO DOUBLE-SCORED: terminal ids are not leasable again — a
    # resumed engine can only pick up the open in-flight set — and a
    # direct transition attempt on a scored job is rejected (evicted
    # terminal docs are not even addressable for mutation)
    digest_before = verdict_digest(store)
    re_leased = store.claim_open_jobs("recoverer", limit=100000,
                                     max_stuck_seconds=0.0)
    assert not ({d.id for d in re_leased} & set(terms))
    for jid in terms:
        with pytest.raises((J.InvalidTransition, KeyError)):
            store.transition(jid, J.PREPROCESS_INPROGRESS)

    # PROVENANCE CHAIN INTACT for every acked terminal verdict
    for jid, verdict in terms.items():
        rec = store.tier.get_prov(jid)
        assert rec is not None, f"provenance lost: {jid}"
        assert rec["job_id"] == jid and rec["verdict"] == verdict
        assert rec["hops"] and rec["hops"][0]["action"] == "scored"

    # REPLAY-TWICE == REPLAY-ONCE over the frozen crashed directory
    store_b = JobStore(tier=JobTier(replay_dir), tier_hot_seconds=0.0,
                       tier_checkpoint_min_seconds=0.0)
    first = store_b.tier.recover(store_b._apply_replay)
    second = store_b.tier.recover(store_b._apply_replay)
    assert second["wal_records_replayed"] == 0
    assert second["wal_records_stale"] == (
        first["wal_records_replayed"] + first["wal_records_stale"])
    assert verdict_digest(store_b) == digest_before


def test_jobstore_soak_disk_chaos_degrades_cleanly(tmp_path):
    """disk=0.2:eio at every WAL/segment append seam: the child keeps
    acking (durability degrades, scoring never stops), and recovery
    over the damaged directory is clean and self-consistent — chaos may
    cost records their durability, never their integrity."""
    store_dir = str(tmp_path / "jobstore")
    proc = _spawn(tmp_path, store_dir, chaos="seed=3;disk=0.2:eio")
    try:
        acks = _run_until_kill(proc, min_acks=400)
    finally:
        proc.kill()
    _preserve(store_dir, "disk-chaos")
    created, _claimed, terms = _parse_acks(acks)
    # degradation is real work continuing: the child kept scoring well
    # past the first injected fault (~20% of appends fault at this rate)
    assert len(terms) >= 80

    store, stats = _recover(store_dir)
    # recovery classifies every surface cleanly (injected EIO aborts an
    # append mid-batch; segfile truncates back to the frame boundary,
    # so the scans must never report corruption)
    assert stats["wal_scan"] in ("ok", "torn_tail"), stats
    assert stats["segment_scan"] in ("ok", "torn_tail"), stats
    # what WAS recovered is a self-consistent subset of the acked
    # stream: acked ids only, statuses the ack stream can explain
    every = store.by_status(*J.OPEN_STATUSES, *J.TERMINAL_STATUSES)
    assert every, "chaos leg recovered nothing"
    for doc in every:
        # durable-but-unacked records are legal (the ack line can die in
        # the pipe); foreign ids are not
        assert doc.id.startswith("soak-"), f"foreign record: {doc.id}"
        if doc.status in J.TERMINAL_STATUSES and doc.id in terms:
            assert terms[doc.id] == doc.status, \
                f"verdict drift under chaos: {doc.id}"
    # the recovered store is immediately writable (the injector died
    # with the child): score one in-flight job through to terminal
    leased = store.claim_open_jobs("recoverer", limit=1,
                                  max_stuck_seconds=0.0)
    if leased:
        jid = leased[0].id
        store.advance(jid, J.PREPROCESS_COMPLETED,
                      J.POSTPROCESS_INPROGRESS)
        store.transition(jid, J.COMPLETED_HEALTH, reason="post-chaos")
        assert store.get(jid).status == J.COMPLETED_HEALTH


def test_jobstore_soak_graceful_shutdown_drains_archive_dirty(tmp_path):
    """The graceful-shutdown leg of the soak (ISSUE 19 satellite 3):
    with a (file) archive attached, release_leases + the final flush
    drain `archive_dirty_count` to ZERO — the gauge the
    `foremastbrain:archive_dirty_count` /metrics row exports."""
    from foremast_tpu.engine.archive import FileArchive

    archive = FileArchive(str(tmp_path / "archive"))
    tier = JobTier(str(tmp_path / "jobstore"))
    store = JobStore(archive=archive, tier=tier, tier_hot_seconds=0.0,
                     tier_checkpoint_min_seconds=0.0)
    for i in range(30):
        jid = f"g-{i:03d}"
        store.create(J.Document(id=jid, app_name="app", strategy="canary",
                                start_time="0", end_time="0"))
    store.claim_open_jobs("w0", limit=10)
    for i in range(10, 20):
        jid = f"g-{i:03d}"
        store.claim_open_jobs("w0", limit=1, only_ids={jid})
        store.advance(jid, J.PREPROCESS_COMPLETED,
                      J.POSTPROCESS_INPROGRESS)
        store.transition(jid, J.COMPLETED_HEALTH, reason="scored")
    assert store.archive_dirty_count() > 0  # open mirrors still pending
    # the graceful-shutdown protocol: surrender leases, then drain
    store.release_leases("w0")
    deadline = time.monotonic() + 30.0
    while store.archive_dirty_count() > 0 and time.monotonic() < deadline:
        store.flush()
        time.sleep(0.05)
    assert store.archive_dirty_count() == 0, \
        "graceful shutdown left archive-dirty docs behind"
    # the drained gauge is what operators watch: both export surfaces
    # (the /metrics row and the /status section) read zero
    from foremast_tpu.service.api import ForemastService

    svc = ForemastService(store=store)
    _code, metrics_body = svc.metrics()
    assert "foremastbrain:archive_dirty_count 0" in metrics_body
    _code, summary = svc.status_summary()
    assert summary["archive_dirty"] == 0
    assert "job_store" in summary  # tier section rides /status too
    store.close()
    # the drained mirror is the real thing: a fresh store over the same
    # archive can adopt the whole released fleet
    store2 = JobStore(archive=FileArchive(str(tmp_path / "archive")))
    adopted = store2.adopt_stale_from_archive(worker="peer", limit=1000)
    assert adopted == 20  # every still-open released job, nothing else
