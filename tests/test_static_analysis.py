"""zoolint gate tests: fixture corpus coverage for every rule, the
suppression and baseline round-trips, the CLI contract, and the
repo-wide CI gate (the library must stay clean vs the committed
baseline, inside the 30s budget).

The corpus in tests/fixtures/lint/ is analyzed, never imported: each
rule has at least one firing snippet and one quiet (``*_ok``) twin.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from analytics_zoo_tpu.analysis import (all_rules, analyze, analyze_file,
                                        default_root, diff_against_baseline,
                                        findings_to_baseline, get_rule,
                                        load_baseline, save_baseline)
from analytics_zoo_tpu.analysis.findings import Suppressions

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")


def fixture_findings(name):
    return analyze_file(os.path.join(FIXTURES, name), rel_to=FIXTURES)


def scopes_of(findings, rule):
    return {f.scope for f in findings if f.rule == rule}


# ---------------------------------------------------------------------------
# rule catalog
# ---------------------------------------------------------------------------


def test_rule_catalog_complete():
    rules = {r.id for r in all_rules()}
    assert rules == {
        "JG-IMPURE-CALL", "JG-GLOBAL-MUT", "JG-HOST-SYNC",
        "JG-TRACED-BRANCH", "JG-JIT-IN-LOOP", "JG-STATIC-UNSTABLE",
        "JG-TRANSFER-HOT", "JG-DONATE-REUSE",
        "THR-GUARD", "THR-BLOCK", "THR-ORDER", "THR-SHARED-MUT",
        "LINT-BARE-DISABLE",
    }
    for r in all_rules():
        assert r.summary and r.hint, f"{r.id} missing summary/hint"
    assert get_rule("THR-GUARD").id == "THR-GUARD"
    assert get_rule("NOPE") is None


# ---------------------------------------------------------------------------
# fixture corpus: every rule fires once and its quiet twin stays quiet
# ---------------------------------------------------------------------------


def test_jg_purity_fixture():
    fs = fixture_findings("jg_purity.py")
    assert scopes_of(fs, "JG-IMPURE-CALL") == {"impure_print"}
    assert scopes_of(fs, "JG-GLOBAL-MUT") == {"global_mut"}
    assert scopes_of(fs, "JG-HOST-SYNC") == {"host_sync"}
    assert scopes_of(fs, "JG-TRACED-BRANCH") == {"traced_branch"}
    # the quiet twins produce nothing at all
    quiet = {"debug_print_ok", "host_print_ok", "global_mut_host_ok",
             "shape_sync_ok", "static_branch_ok"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 4


def test_jg_compile_fixture():
    fs = fixture_findings("jg_compile.py")
    assert scopes_of(fs, "JG-JIT-IN-LOOP") == {"jit_in_loop"}
    assert scopes_of(fs, "JG-STATIC-UNSTABLE") == {"static_unstable"}
    assert scopes_of(fs, "JG-DONATE-REUSE") == {"donate_reuse"}
    quiet = {"jit_hoisted_ok", "static_hashable_ok", "donate_rebind_ok"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 3


def test_transfer_hot_fires_only_in_hot_modules():
    hot = fixture_findings("hot_path.py")
    assert scopes_of(hot, "JG-TRANSFER-HOT") == \
        {"per_batch_sync", "per_batch_device_get"}
    assert "epoch_sync_ok" not in {f.scope for f in hot}
    assert len(hot) == 2
    # identical loop body, no hot-path marker -> silent
    assert fixture_findings("cold_path.py") == []


def test_table_exchange_fixture():
    """The sharded embedding-table exchange idiom behind
    parallel/table_sharding.py: assembling a row-sharded lookup by
    hauling each model shard's partial rows to the host (or draining
    dispatch per shard) fires JG-TRANSFER-HOT; the shipped lookup —
    one on-device psum exchange, one sync on the combined handle —
    stays quiet, so the giant-table serving path keeps a clean lint
    bill by construction."""
    fs = fixture_findings("table_exchange.py")
    assert scopes_of(fs, "JG-TRANSFER-HOT") == \
        {"per_shard_host_exchange", "per_shard_drain"}
    assert "psum_exchange_ok" not in {f.scope for f in fs}
    assert len(fs) == 2


def test_concurrency_fixture():
    fs = fixture_findings("threads.py")
    assert scopes_of(fs, "THR-GUARD") == {"Counter.snapshot"}
    assert scopes_of(fs, "THR-BLOCK") == {"Waiter.sleep_under_lock"}
    assert scopes_of(fs, "THR-ORDER") == {"TwoLocks.fwd", "TwoLocks.rev"}
    assert scopes_of(fs, "THR-SHARED-MUT") == {"Producer._run"}
    quiet = {"Counter.snapshot_locked_ok", "Waiter.sleep_outside_ok",
             "Waiter.wait_on_held_cv_ok", "OneOrder.first",
             "OneOrder.second", "LockedProducer._run",
             "LockedProducer.result"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 5


def test_hot_cache_fixture():
    """The hot-row cache frequency-counter idiom (parallel/
    hot_cache.py): the batcher thread bumping the shared counter / hot
    set with no lock fires THR-SHARED-MUT — a torn read would replicate
    the wrong rows; the shipped mutate-under-lock, replace-wholesale
    twin stays quiet, so the cache keeps a clean lint bill by
    construction, not by suppression."""
    fs = fixture_findings("hot_cache.py")
    assert scopes_of(fs, "THR-SHARED-MUT") == {"NaiveHotCounter._run"}
    quiet = {"LockedHotCounter._run", "LockedHotCounter.top_ids"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 1


def test_shm_ring_fixture():
    """The ring-buffer idiom behind deploy/shmqueue.py: an unlocked
    cross-thread cursor write fires THR-SHARED-MUT; the shipped
    claim-under-condition protocol stays quiet — so the zero-copy queue
    keeps a clean lint bill by construction, not by suppression."""
    fs = fixture_findings("shm_ring.py")
    assert scopes_of(fs, "THR-SHARED-MUT") == {"NaiveRing._run"}
    quiet = {"LockedRing._run", "LockedRing.free_slots"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 1


def test_compile_cache_fixture():
    """The compile-cache ledger idiom (deploy/compile_cache.py): an
    unlocked cross-thread hit/miss bump on the load path fires
    THR-GUARD; the shipped lock-held twin stays quiet — so the cache
    stats the warm-start proof reads keep a clean lint bill by
    construction, not by suppression."""
    fs = fixture_findings("compile_cache.py")
    assert scopes_of(fs, "THR-GUARD") == {"NaiveCompileCache.load"}
    quiet = {"LockedCompileCache.store", "LockedCompileCache.load",
             "NaiveCompileCache.store"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 1


def test_stream_uploader_fixture():
    """The STREAM shard-uploader idiom (data/streaming.ShardUploader):
    unlocked cross-thread upload stats fire THR-SHARED-MUT, and a
    training loop that blocks on every shard's upload fires
    JG-TRANSFER-HOT; the shipped protocol — lock-guarded stats, the
    slot-recycle wait paid on the uploader's own thread, one sync per
    epoch — stays quiet, so the streaming tier keeps a clean lint bill
    by construction."""
    fs = fixture_findings("stream_uploader.py")
    assert scopes_of(fs, "THR-SHARED-MUT") == {"NaiveUploader._run"}
    assert scopes_of(fs, "JG-TRANSFER-HOT") == {"naive_rotation"}
    quiet = {"LockedUploader._run", "LockedUploader.stats",
             "rotation_ok"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 2


def test_fused_kernel_driver_fixture():
    """The kernel-sweep driver idiom (a loop over a kernel's tiles):
    draining every tile with a per-iteration block_until_ready fires
    JG-TRANSFER-HOT; the shipped drivers enqueue the sweep and sync
    once on the last handle — quiet by construction."""
    fs = fixture_findings("fused_kernel.py")
    assert scopes_of(fs, "JG-TRANSFER-HOT") == {"per_tile_block"}
    assert "batched_tiles_ok" not in {f.scope for f in fs}
    assert len(fs) == 1


def test_ring_step_fixture():
    """The ring-attention hop-loop idiom (ops/ring_attention.py):
    draining the device after every ppermute hop fires JG-TRANSFER-HOT
    — a per-step sync forfeits exactly the transfer/compute overlap the
    double-buffered schedule exists for; the shipped
    issue-next-hop-then-fold twin with ONE sync after the ring stays
    quiet, so the sequence-parallel path keeps a clean lint bill by
    construction."""
    fs = fixture_findings("ring_step.py")
    assert scopes_of(fs, "JG-TRANSFER-HOT") == {"per_hop_sync"}
    assert "double_buffered_ok" not in {f.scope for f in fs}
    assert len(fs) == 1


def test_mesh_data_cursor_fixture():
    """The per-host data-tier shard cursor (multi-controller
    _fit_stream): an uploader thread advancing the elastic-resume
    cursor with no lock fires THR-SHARED-MUT — a torn read would hand
    the checkpoint manifest a mid-rotation cursor; the shipped
    advance-and-snapshot-under-one-lock protocol stays quiet, so the
    mesh-aware data tier keeps a clean lint bill by construction."""
    fs = fixture_findings("mesh_data.py")
    assert scopes_of(fs, "THR-SHARED-MUT") == {"NaiveShardCursor._run"}
    quiet = {"LockedShardCursor._run", "LockedShardCursor.manifest"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 1


def test_roster_fixture():
    """The pod host-roster idiom (core/context.HostRoster behind the
    PodCoordinator): a supervisor thread marking a host lost with no
    lock fires THR-SHARED-MUT — a torn read could dispatch onto a
    half-dead mesh replica; the shipped
    mutate-and-read-under-one-lock-with-an-epoch-tag protocol stays
    quiet, so the failure-domain bookkeeping keeps a clean lint bill by
    construction."""
    fs = fixture_findings("roster.py")
    assert scopes_of(fs, "THR-SHARED-MUT") == {"NaiveRoster._run"}
    quiet = {"EpochRoster._run", "EpochRoster.healed"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 1


def test_observe_instrumentation_fixture():
    """Span/metric instrumentation idioms: the naive retrofit fires
    (unlocked ring read, per-step host sync for a metric sample); the
    idiom observe/ actually uses — locked plain fields, deque ring,
    wall-clock-only timing in the loop — stays clean, so instrumenting
    a pipeline never costs a THR-GUARD/JG-TRANSFER-HOT finding."""
    fs = fixture_findings("observe_spans.py")
    assert scopes_of(fs, "THR-GUARD") == {"NaiveRing.snapshot"}
    assert scopes_of(fs, "JG-TRANSFER-HOT") == {"record_step_metric_naive"}
    quiet = {"SpanRing.finish", "SpanRing.snapshot",
             "SpanRing.completed_count", "record_step_metric_ok"}
    assert not quiet & {f.scope for f in fs}
    assert len(fs) == 2


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_reasoned_disable_silences_bare_disable_reported():
    fs = fixture_findings("suppress.py")
    # both reads are THR-GUARD violations; both disables silence them...
    assert scopes_of(fs, "THR-GUARD") == set()
    # ...but the bare one is itself a finding, pointing at its line
    bare = [f for f in fs if f.rule == "LINT-BARE-DISABLE"]
    assert len(bare) == 1 and len(fs) == 1
    assert "THR-GUARD" in bare[0].message


def test_suppression_parser_reasons_and_lists():
    src = (
        "a = 1  # zoolint: disable=THR-GUARD(wait() joins the writer), "
        "JG-HOST-SYNC\n"
        "b = 2  # zoolint: disable=ALL(generated code)\n"
    )
    sup = Suppressions(src)
    assert sup.by_line[1] == {
        "THR-GUARD": "wait() joins the writer",  # nested parens survive
        "JG-HOST-SYNC": None,
    }
    assert sup.by_line[2] == {"ALL": "generated code"}
    bare = sup.bare_disable_findings("x.py")
    assert [f.line for f in bare] == [1]  # only the reasonless entry


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------


def test_baseline_roundtrip(tmp_path):
    fs = fixture_findings("threads.py")
    path = str(tmp_path / "baseline.json")
    save_baseline(path, fs)
    accepted = load_baseline(path)
    # accepting exactly these findings gates to zero new, zero stale
    new, stale = diff_against_baseline(fs, accepted)
    assert new == [] and stale == []
    # baseline keys are line-free: rule :: path :: scope :: message
    assert all(len(k.split(" :: ")) == 4 for k in accepted)
    # dropping one accepted entry resurfaces exactly that finding
    k0 = sorted(accepted)[0]
    partial = {k: v for k, v in accepted.items() if k != k0}
    new, stale = diff_against_baseline(fs, partial)
    assert len(new) == 1 and " :: ".join(new[0].key()) == k0
    # an entry the code no longer produces is reported stale
    extra = dict(accepted)
    extra["THR-GUARD :: gone.py :: X.y :: vanished"] = 1
    new, stale = diff_against_baseline(fs, extra)
    assert new == [] and stale == ["THR-GUARD :: gone.py :: X.y :: vanished"]


def test_baseline_counts_duplicates():
    fs = fixture_findings("threads.py")
    accepted = {k: v for k, v in
                findings_to_baseline(fs)["accepted"].items()}
    doubled = fs + fs
    new, _ = diff_against_baseline(doubled, accepted)
    assert len(new) == len(fs)  # second copies exceed the counts


def test_missing_baseline_is_empty():
    assert load_baseline("/nonexistent/baseline.json") == {}


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.analysis", *args],
        capture_output=True, text=True, timeout=120)


def test_cli_exit_codes_and_json():
    dirty = os.path.join(FIXTURES, "threads.py")
    clean = os.path.join(FIXTURES, "cold_path.py")
    assert _run_cli(clean).returncode == 0
    r = _run_cli(dirty, "--json")
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert data["files"] == 1 and data["counts"]["THR-GUARD"] == 1
    assert all({"rule", "path", "line", "scope", "message", "hint"}
               <= set(f) for f in data["findings"])
    rules = _run_cli("--list-rules")
    assert rules.returncode == 0 and "JG-DONATE-REUSE" in rules.stdout


def test_cli_check_gate_against_fixture_baseline(tmp_path):
    dirty = os.path.join(FIXTURES, "threads.py")
    bl = str(tmp_path / "bl.json")
    # --write-baseline accepts today's findings; --check then passes
    assert _run_cli(dirty, "--write-baseline", "--baseline", bl).returncode == 0
    assert _run_cli(dirty, "--check", "--baseline", bl).returncode == 0
    # a NEW violation (not in baseline) fails the gate
    assert _run_cli(dirty, "--check", "--baseline",
                    str(tmp_path / "empty.json")).returncode == 1


# ---------------------------------------------------------------------------
# the repo-wide CI gate
# ---------------------------------------------------------------------------


def test_repo_is_clean_against_committed_baseline():
    """The actual CI gate: linting the whole library produces nothing
    beyond lint_baseline.json, in well under the 30s budget."""
    root = default_root()
    t0 = time.monotonic()
    findings = analyze([root])
    elapsed = time.monotonic() - t0
    accepted = load_baseline(
        os.path.join(os.path.dirname(root), "lint_baseline.json"))
    new, _stale = diff_against_baseline(findings, accepted)
    assert new == [], "new zoolint findings:\n" + \
        "\n".join(f.render() for f in new)
    assert elapsed < 30.0, f"zoolint took {elapsed:.1f}s (budget 30s)"
