"""Manager state-machine tests with mocked control-plane RPC (reference:
torchft/manager_test.py: patched ManagerClient + autospec'd ProcessGroup
drive the Manager through happy path, heal, errors, and commit gating
without any networking)."""

from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from torchft_tpu.coordination import QuorumResult
from torchft_tpu.manager import (
    ExceededMaxRetriesError,
    Manager,
    WorldSizeMode,
)
from torchft_tpu.process_group import ProcessGroupDummy


def make_quorum_result(**kwargs) -> QuorumResult:
    defaults = dict(
        quorum_id=1,
        replica_rank=0,
        replica_world_size=2,
        recover_src_manager_address="",
        recover_src_replica_rank=None,
        recover_dst_replica_ranks=[],
        store_address="127.0.0.1:1234",
        max_step=0,
        max_replica_rank=0,
        max_world_size=2,
        heal=False,
        commit_failures=0,
    )
    defaults.update(kwargs)
    return QuorumResult(**defaults)


def make_manager(pg=None, quorum_result=None, **kwargs):
    """Builds a Manager with mocked ManagerServer/Client and transport."""
    pg = pg if pg is not None else ProcessGroupDummy()
    transport = MagicMock()
    transport.metadata.return_value = "http://127.0.0.1:0"
    with patch("torchft_tpu.manager.ManagerServer") as server_cls, patch(
        "torchft_tpu.manager.ManagerClient"
    ) as client_cls:
        server_cls.return_value.address.return_value = "127.0.0.1:1"
        client = client_cls.return_value
        client._quorum.return_value = quorum_result or make_quorum_result()
        # Echo the local vote by default.
        client.should_commit.side_effect = (
            lambda rank, step, ok, timeout=None, trace_id="": ok
        )
        client.drain_status.return_value = False
        manager = Manager(
            pg=pg,
            checkpoint_transport=transport,
            replica_id="test",
            lighthouse_addr="unused:1",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=kwargs.pop("use_async_quorum", True),
            **kwargs,
        )
    manager._test_client = client  # type: ignore[attr-defined]
    manager._test_transport = transport  # type: ignore[attr-defined]
    return manager


def test_happy_path_commit():
    pg = ProcessGroupDummy()
    m = make_manager(pg=pg)
    try:
        m.start_quorum()
        arr = np.full(4, 2.0, dtype=np.float32)
        out = m.allreduce(arr).wait()
        # Dummy pg: sum = input; divided by num_participants (2).
        np.testing.assert_allclose(out[0], 1.0)
        assert m.should_commit()
        assert m.current_step() == 1
        assert m.batches_committed() == 2
        assert pg.configure_count == 1  # quorum_id changed from -1 -> 1
    finally:
        m.shutdown()


def test_pg_reconfigured_only_on_quorum_change():
    pg = ProcessGroupDummy()
    m = make_manager(pg=pg)
    try:
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 1
        # Same quorum id -> no reconfigure.
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 1
        # New quorum id -> reconfigure with prefixed store path.
        m._test_client._quorum.return_value = make_quorum_result(quorum_id=2)
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 2
    finally:
        m.shutdown()


def test_async_heal_defers_user_state():
    user_state = {"w": np.arange(3.0)}
    loaded = {}
    m = make_manager(
        quorum_result=make_quorum_result(
            heal=True,
            max_step=7,
            recover_src_manager_address="127.0.0.1:9",
            recover_src_replica_rank=1,
        )
    )
    m._test_transport.recv_checkpoint.return_value = {
        "torchft": {"step": 7, "batches_committed": 14},
        "user": {"default": user_state},
    }
    m.register_state_dict_fn(
        "default", lambda: user_state, lambda s: loaded.update(s)
    )
    with patch("torchft_tpu.manager.ManagerClient") as peer_cls:
        peer_cls.return_value._checkpoint_metadata.return_value = "http://peer"
        try:
            m.start_quorum()
            m.wait_quorum()
            # Healing rank doesn't participate in async mode.
            assert not m.is_participating()
            assert m.num_participants() == 2
            # torchft state applied immediately; user state deferred.
            assert m.current_step() == 7
            assert not loaded
            assert m.should_commit()
            assert loaded  # applied at commit time
            assert m.current_step() == 8
        finally:
            m.shutdown()


def test_sync_quorum_applies_state_immediately():
    loaded = {}
    m = make_manager(
        use_async_quorum=False,
        quorum_result=make_quorum_result(
            heal=True,
            max_step=3,
            recover_src_manager_address="127.0.0.1:9",
            recover_src_replica_rank=1,
        ),
    )
    m._test_transport.recv_checkpoint.return_value = {
        "torchft": {"step": 3, "batches_committed": 6},
        "user": {"default": {"x": 1}},
    }
    m.register_state_dict_fn(
        "default", lambda: {}, lambda s: loaded.update(s)
    )
    with patch("torchft_tpu.manager.ManagerClient") as peer_cls:
        peer_cls.return_value._checkpoint_metadata.return_value = "http://peer"
        try:
            m.start_quorum()  # sync: waits and applies
            assert loaded == {"x": 1}
            assert m.current_step() == 3
            # Sync mode participates even while healing.
            assert m.is_participating()
        finally:
            m.shutdown()


def test_send_checkpoint_to_recovering_peers():
    m = make_manager(
        quorum_result=make_quorum_result(recover_dst_replica_ranks=[1], max_step=5)
    )
    try:
        m.start_quorum()
        m.wait_quorum()
        call = m._test_transport.send_checkpoint.call_args
        assert call.kwargs["dst_ranks"] == [1]
        assert call.kwargs["step"] == 5
    finally:
        m.shutdown()


def test_allreduce_error_latches_and_commit_fails():
    pg = MagicMock()
    pg.errored.return_value = None
    pg.allreduce.side_effect = RuntimeError("collective died")
    m = make_manager(pg=pg)
    try:
        m.start_quorum()
        arr = np.ones(2, dtype=np.float32)
        m.allreduce(arr).wait()  # DummyWork, no raise
        assert m.errored() is not None
        assert not m.should_commit()
        assert m.current_step() == 0
        # Next quorum resets the error.
        pg.allreduce.side_effect = None
        m._test_client._quorum.return_value = make_quorum_result(quorum_id=1)
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is None
    finally:
        m.shutdown()


def test_pg_async_error_surfaces():
    pg = ProcessGroupDummy()
    m = make_manager(pg=pg)
    try:
        m.start_quorum()
        m.wait_quorum()
        pg_err = RuntimeError("async pg failure")
        pg.errored = lambda: pg_err  # type: ignore[method-assign]
        assert m.errored() is pg_err
        assert not m.should_commit()
    finally:
        m.shutdown()


def test_quorum_rpc_failure_is_latched():
    m = make_manager()
    m._test_client._quorum.side_effect = TimeoutError("lighthouse down")
    try:
        m.start_quorum()
        arr = np.ones(1)
        m.allreduce(arr).wait()  # no crash
        assert isinstance(m.errored(), TimeoutError)
        assert not m.should_commit()
    finally:
        m.shutdown()


def test_abort_pending_quorum_interrupts_sync_wait():
    """A drain abort interrupts a BLOCKED sync quorum wait promptly
    (full-job preemption: the peers this quorum is waiting for already
    drained, so the wait could never end) — and the manager is left
    drainable: leave() still works."""
    import threading

    from torchft_tpu.coordination import RequestAborted

    m = make_manager(use_async_quorum=False)
    client = m._test_client
    wake = threading.Event()

    def blocked_quorum(**kw):
        wake.wait(30.0)
        raise RequestAborted("aborted")  # what the killed socket yields

    client._quorum.side_effect = blocked_quorum
    client.abort.side_effect = wake.set
    try:
        errs = []

        def run():
            try:
                m.start_quorum()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # Wait until the RPC is actually pending, then abort.
        import time as _time

        deadline = _time.time() + 5.0
        while not m._quorum_rpc_pending:
            _time.sleep(0.005)
            assert _time.time() < deadline, "RPC never started"
        assert m.abort_pending_quorum() is True
        t.join(5.0)
        assert not t.is_alive(), "sync quorum wait did not abort"
        assert isinstance(errs[0], RequestAborted)
        assert isinstance(m.errored(), RequestAborted)  # fails fast
        client.leave.return_value = True
        assert m.leave() is True
    finally:
        m.shutdown()


def test_drain_requested_falls_back_to_status_rpc_on_error():
    """The quorum-response piggyback only delivers on quorum SUCCESS; an
    errored manager (peers drained first -> its quorums keep failing)
    must learn the operator drain from the out-of-band drain_status
    read, or a whole-job drain_all strands it retrying unwinnable
    quorums."""
    m = make_manager()
    client = m._test_client
    try:
        assert m.drain_requested() is False
        client.drain_status.assert_not_called()  # healthy: piggyback only
        m.report_error(RuntimeError("quorum failed"))
        client.drain_status.return_value = True
        assert m.drain_requested() is True
        client.drain_status.assert_called_once()
        # Latched: no second RPC.
        assert m.drain_requested() is True
        client.drain_status.assert_called_once()
    finally:
        m.shutdown()


def test_drain_requested_journals_failed_status_probe(tmp_path, monkeypatch):
    """The errored-manager drain_status fallback hitting a dead
    lighthouse must not swallow the failure invisibly: each failed probe
    is journaled as ``rpc_retry`` (rpc=drain_status) and the next call
    retries — a pending operator drain can go dark, never silently
    masked."""
    import json

    from torchft_tpu import telemetry

    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    telemetry.reset_event_log()
    try:
        m = make_manager()
        client = m._test_client
        try:
            m.report_error(RuntimeError("quorum failed"))
            client.drain_status.side_effect = TimeoutError("lighthouse gone")
            assert m.drain_requested() is False
            assert m.drain_requested() is False  # retried, not latched off
            assert client.drain_status.call_count == 2
            client.drain_status.side_effect = None
            client.drain_status.return_value = True
            assert m.drain_requested() is True  # recovers once RPC heals
        finally:
            m.shutdown()
    finally:
        telemetry.reset_event_log()

    with open(path) as fh:
        events = [json.loads(line) for line in fh]
    probes = [e for e in events if e["event"] == "rpc_retry"]
    assert len(probes) == 2
    assert probes[0]["attrs"]["rpc"] == "drain_status"
    assert probes[0]["attrs"]["cause"] == "TimeoutError"


def test_start_quorum_after_drain_abort_never_waits():
    """Once a drain abort fired, any later start_quorum aborts before
    issuing the RPC — the signal won the race to before the wait."""
    from torchft_tpu.coordination import RequestAborted

    m = make_manager(use_async_quorum=False)
    try:
        assert m.abort_pending_quorum() is False  # nothing in flight
        with pytest.raises(RequestAborted):
            m.start_quorum()
        m._test_client._quorum.assert_not_called()
    finally:
        m.shutdown()


def test_min_replica_size_gates_commit():
    m = make_manager(
        min_replica_size=3,
        quorum_result=make_quorum_result(replica_world_size=2, max_world_size=2),
    )
    try:
        m.start_quorum()
        m.wait_quorum()
        assert not m.should_commit()  # 2 < 3
    finally:
        m.shutdown()


def test_fixed_with_spares_benches_extra_ranks():
    m = make_manager(
        min_replica_size=2,
        world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        quorum_result=make_quorum_result(
            replica_rank=2, max_world_size=3, replica_world_size=3
        ),
    )
    try:
        m.start_quorum()
        m.wait_quorum()
        assert m.num_participants() == 2  # clamped to fixed size
        assert not m.is_participating()  # rank 2 is a spare
        arr = np.full(2, 5.0)
        out = m.allreduce(arr).wait()
        np.testing.assert_allclose(out[0], 0.0)  # spare contributes zeros
    finally:
        m.shutdown()


def test_max_retries_raises():
    m = make_manager(max_retries=1)
    m._test_client.should_commit.side_effect = None
    m._test_client.should_commit.return_value = False
    try:
        m.start_quorum()
        assert not m.should_commit()
        m.start_quorum()
        with pytest.raises(ExceededMaxRetriesError):
            m.should_commit()
    finally:
        m.shutdown()


def test_commit_failures_reported_to_quorum():
    m = make_manager()
    m._test_client.should_commit.side_effect = None
    m._test_client.should_commit.return_value = False
    try:
        m.start_quorum()
        assert not m.should_commit()
        m.start_quorum()
        m.wait_quorum()
        kwargs = m._test_client._quorum.call_args.kwargs
        assert kwargs["commit_failures"] == 1
    finally:
        m.shutdown()


def test_state_dict_roundtrip():
    m = make_manager()
    try:
        m.load_state_dict({"step": 42, "batches_committed": 84})
        assert m.current_step() == 42
        assert m.state_dict() == {"step": 42, "batches_committed": 84}
    finally:
        m.shutdown()


def test_set_state_dict_fns_single_registry():
    """Reference-parity alias: one load/save pair for the whole user state
    (reference: manager.py set_state_dict_fns)."""
    m = make_manager()
    loaded = []
    try:
        m.set_state_dict_fns(loaded.append, lambda: {"w": 7})
        assert m._manager_state_dict()["user"]["default"] == {"w": 7}
        m._load_state_dicts["default"]({"w": 9})
        assert loaded == [{"w": 9}]
    finally:
        m.shutdown()


def test_wrap_future_swallow_and_timeout():
    """wrap_future (reference parity): failures and timeouts latch an error
    and resolve to the default instead of raising."""
    import concurrent.futures

    m = make_manager()
    try:
        # Success passes through.
        ok = concurrent.futures.Future()
        ok.set_result(7)
        assert m.wrap_future(ok, default=-1).result(timeout=5) == 7
        assert m.errored() is None

        # Failure: swallowed to default, error latched.
        bad = concurrent.futures.Future()
        bad.set_exception(RuntimeError("collective died"))
        assert m.wrap_future(bad, default=-1).result(timeout=5) == -1
        assert m.errored() is not None

        # Timeout: same contract.
        m2 = make_manager()
        try:
            never = concurrent.futures.Future()
            assert (
                m2.wrap_future(never, default=-2, timeout=0.2).result(
                    timeout=5
                )
                == -2
            )
            assert isinstance(m2.errored(), TimeoutError)
        finally:
            m2.shutdown()
    finally:
        m.shutdown()


def test_goodput_accounting():
    """goodput() splits wall time between commit gates by outcome: a
    latched error turns that window into failed_s, clean gates into
    committed_s, and the fraction reflects the split."""
    import time as _time

    m = make_manager()
    try:
        m.start_quorum()
        assert m.should_commit() is True  # first gate: unattributed
        _time.sleep(0.05)
        m.start_quorum()
        assert m.should_commit() is True  # ~50ms committed
        m.start_quorum()
        m.report_error(RuntimeError("injected"))
        _time.sleep(0.05)
        assert m.should_commit() is False  # ~50ms failed
        g = m.goodput()
        assert g["committed_steps"] == 2
        assert g["failed_commits"] == 1
        assert g["committed_s"] > 0 and g["failed_s"] > 0
        assert 0.0 < g["goodput_frac"] < 1.0
        assert g["heal_count"] == 0
    finally:
        m.shutdown()


def test_goodput_frac_none_before_first_gate():
    """The window before the first commit gate is unattributed: every
    bucket stays zero and goodput_frac is None — not 0.0, which would
    read as 'all time lost'."""
    m = make_manager()
    try:
        g = m.goodput()
        assert g["goodput_frac"] is None
        assert g["committed_steps"] == 0 and g["failed_commits"] == 0
        assert g["committed_s"] == 0.0 and g["failed_s"] == 0.0
        assert g["heal_count"] == 0 and g["heal_s"] == 0.0
        # Still None after a quorum forms but before any gate.
        m.start_quorum()
        m.wait_quorum()
        assert m.goodput()["goodput_frac"] is None
    finally:
        m.shutdown()


def test_goodput_commit_fail_heal_bucketing():
    """A commit -> fail -> heal sequence lands in the right buckets: a
    clean gate adds to committed_s, a latched error turns its window into
    failed_s, and the checkpoint recv lands in heal_s — excluded from the
    surrounding window's outcome bucket (manager._heal_since_gate)."""
    import time as _time

    m = make_manager()
    try:
        # Gate 1 opens the accounting window; gate 2 commits ~40ms.
        m.start_quorum()
        assert m.should_commit() is True
        _time.sleep(0.04)
        m.start_quorum()
        assert m.should_commit() is True
        # Latched error -> the next window is failed time.
        m.start_quorum()
        m.report_error(RuntimeError("injected"))
        _time.sleep(0.04)
        assert m.should_commit() is False

        # Heal quorum: recv_checkpoint sleeps so heal_s is measurable.
        def slow_recv(**kwargs):
            _time.sleep(0.05)
            return {
                "torchft": {"step": 9, "batches_committed": 18},
                "user": {},
            }

        m._test_transport.recv_checkpoint.side_effect = slow_recv
        m._test_client._quorum.return_value = make_quorum_result(
            quorum_id=2,
            heal=True,
            max_step=9,
            recover_src_manager_address="127.0.0.1:9",
            recover_src_replica_rank=1,
        )
        with patch("torchft_tpu.manager.ManagerClient") as peer_cls:
            peer_cls.return_value._checkpoint_metadata.return_value = (
                "http://peer"
            )
            m.start_quorum()
            m.wait_quorum()
        assert m.should_commit() is True

        g = m.goodput()
        assert g["committed_steps"] == 3
        assert g["failed_commits"] == 1
        assert g["heal_count"] == 1
        assert g["heal_s"] >= 0.05
        assert g["committed_s"] > 0 and g["failed_s"] > 0
        # frac is consistent with the buckets, heal time in the denominator.
        denom = g["committed_s"] + g["failed_s"] + g["heal_s"]
        assert g["goodput_frac"] == round(g["committed_s"] / denom, 4)
        assert 0.0 < g["goodput_frac"] < 1.0
    finally:
        m.shutdown()


def test_goodput_ledger_tiles_wall_clock():
    """The TimeLedger audit fix: the per-kind accounts in goodput() must
    tile the accounted wall clock to 1e-6 across commit/fail/heal/drain
    outcomes — the legacy committed/failed/heal buckets are a derived
    view, the ledger is authoritative. The residual of each window is
    routed by outcome: first gate -> init_compile, failed gate ->
    discarded_step, clean gate -> compute."""
    import time as _time

    from torchft_tpu.telemetry import BADPUT_KINDS

    m = make_manager()
    shut = False
    try:
        m.start_quorum()
        assert m.should_commit() is True  # first gate -> init_compile
        _time.sleep(0.03)
        m.start_quorum()
        assert m.should_commit() is True  # clean window -> compute
        m.start_quorum()
        m.report_error(RuntimeError("injected"))
        _time.sleep(0.03)
        assert m.should_commit() is False  # failed -> discarded_step

        g = m.goodput()
        badput = g["badput_s"]
        assert set(badput) == set(BADPUT_KINDS)
        assert g["tiling_error_s"] < 1e-6
        assert badput["init_compile"] > 0.0
        assert badput["compute"] > 0.0
        assert badput["discarded_step"] > 0.0
        assert badput["quorum_wait"] >= 0.0
        assert 0.0 < g["ledger_goodput_frac"] < 1.0
        # The exposed dict is rounded for humans; the live ledger holds
        # the exact invariant.
        assert m._ledger.tiling_error_s() < 1e-6
        assert m._ledger.total_s() == pytest.approx(
            sum(m._ledger.totals().values()), abs=1e-6)

        # Shutdown accounts the tail window as drain — accounted time
        # keeps covering wall clock right up to process exit.
        m.shutdown()
        shut = True
        t = m._ledger.totals()
        assert t["drain"] > 0.0
        assert m._ledger.tiling_error_s() < 1e-6
    finally:
        if not shut:
            m.shutdown()


def test_wrap_future_completes_even_if_report_error_raises():
    """If report_error (or the logger) raises on the callback thread, the
    wrapped future must still resolve to the default — otherwise the
    caller's wait() hangs to its own timeout (advisor finding r2,
    manager.py wrap_future)."""
    import concurrent.futures

    m = make_manager()
    try:
        def boom(exc):
            raise ValueError("report_error itself blew up")

        m.report_error = boom
        bad = concurrent.futures.Future()
        bad.set_exception(RuntimeError("collective died"))
        assert m.wrap_future(bad, default=-3).result(timeout=5) == -3
    finally:
        m.shutdown()


def test_fenced_state_dict_excludes_snapshot_reads():
    """While the fence is held, _manager_state_dict (the checkpoint-send
    snapshot) must block — and time out rather than read a torn
    (params, step) pair."""
    import threading

    m = make_manager()
    try:
        m.register_state_dict_fn("w", lambda: {"x": 1}, lambda s: None)
        m._timeout = 0.5  # short lock timeout for the reader
        results = {}

        with m.fenced_state_dict():
            def reader():
                try:
                    results["snap"] = m._manager_state_dict()
                except Exception as e:  # noqa: BLE001
                    results["err"] = type(e).__name__

            t = threading.Thread(target=reader)
            t.start()
            t.join(timeout=5)
            assert not t.is_alive()
        # Reader could not snapshot inside the fence.
        assert "snap" not in results
        # After release, snapshots work again.
        assert m._manager_state_dict()["user"]["w"] == {"x": 1}
    finally:
        m.shutdown()


def test_disallow_state_dict_read_raises_on_timeout():
    """A failed write-lock acquisition must raise, never proceed unfenced."""
    m = make_manager()
    try:
        m._timeout = 0.3
        assert m._state_dict_lock.acquire_read(1.0)  # a stuck reader
        try:
            with pytest.raises(TimeoutError):
                m.disallow_state_dict_read()
        finally:
            m._state_dict_lock.release_read()
    finally:
        m.shutdown()


def test_state_dict_lock_blocks_checkpoint_read():
    m = make_manager()
    try:
        m.register_state_dict_fn("default", lambda: {"x": 1}, lambda s: None)
        m.disallow_state_dict_read()
        with pytest.raises(TimeoutError):
            m._state_dict_lock.r_lock(timeout=0.1).__enter__()
        m.allow_state_dict_read()
        assert m._manager_state_dict()["user"]["default"] == {"x": 1}
    finally:
        m.shutdown()


def test_hot_paths_emit_spans_and_metrics(tmp_path, monkeypatch):
    """The reference wraps every hot path in record_function spans
    (manager.py:379-793); here trace_span feeds span_stats, and
    should_commit emits a metrics line when TORCHFT_METRICS_FILE is set."""
    import json

    from torchft_tpu import telemetry

    path = str(tmp_path / "metrics.jsonl")
    monkeypatch.setenv("TORCHFT_METRICS_FILE", path)
    telemetry.reset_span_stats()
    m = make_manager()
    try:
        m.start_quorum()
        m.allreduce(np.ones(4, np.float32)).wait()
        assert m.should_commit()
    finally:
        m.shutdown()
    stats = telemetry.span_stats()
    for name in (
        "torchft::manager::start_quorum",
        "torchft::manager::_async_quorum",
        "torchft::manager::allreduce",
        "torchft::manager::should_commit",
    ):
        assert stats[name]["count"] >= 1, name
    rec = json.loads(open(path).readline())
    assert rec["committed"] == 1.0 and rec["num_participants"] == 2.0


# ---------------------------------------------------------------------------
# _ManagedWork (reference: managed_work_test.py — callback/normalization
# semantics of the managed allreduce handle)
# ---------------------------------------------------------------------------


def test_managed_work_divides_on_wait_only():
    """The divide-by-N is DEFERRED to wait() (reference lazy chain,
    manager.py:973-1251): until then the arrays hold raw sums."""
    from torchft_tpu.manager import _ManagedWork
    from torchft_tpu.work import DummyWork

    m = make_manager()
    try:
        arrays = [np.full(4, 6.0, np.float32)]
        work = _ManagedWork(m, DummyWork(arrays), arrays, scale=1.0 / 3)
        np.testing.assert_allclose(arrays[0], 6.0)  # not yet normalized
        out = work.wait(timeout=5)
        np.testing.assert_allclose(out[0], 2.0)
        # Idempotent: a second wait must not divide again.
        out = work.wait(timeout=5)
        np.testing.assert_allclose(out[0], 2.0)
    finally:
        m.shutdown()


def test_managed_work_failure_latches_and_returns_inputs():
    """A failed collective returns the (unreduced) inputs and latches the
    error on the manager — never raises into the train loop."""
    from torchft_tpu.manager import _ManagedWork
    from torchft_tpu.work import ErrorWork

    m = make_manager()
    try:
        arrays = [np.full(4, 5.0, np.float32)]
        work = _ManagedWork(
            m, ErrorWork(RuntimeError("ring died")), arrays, scale=0.5
        )
        out = work.wait(timeout=5)
        np.testing.assert_allclose(out[0], 5.0)  # unscaled originals
        assert m.errored() is not None
    finally:
        m.shutdown()


def test_managed_work_replace_mode():
    """in_place=False (jax path): wait() returns the work's RESULT arrays,
    not the inputs."""
    from torchft_tpu.manager import _ManagedWork
    from torchft_tpu.work import DummyWork

    m = make_manager()
    try:
        inputs = [np.zeros(3, np.float32)]
        result = [np.full(3, 9.0, np.float32)]
        work = _ManagedWork(
            m, DummyWork(result), inputs, scale=1.0, in_place=False
        )
        out = work.wait(timeout=5)
        np.testing.assert_allclose(out[0], 9.0)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# The gate says why
# ---------------------------------------------------------------------------


def _journaled(tmp_path, monkeypatch, drive, **manager_kwargs):
    """Runs ``drive(manager)`` with a journal; returns its events."""
    import json

    from torchft_tpu import telemetry

    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    telemetry.reset_event_log()
    try:
        m = make_manager(**manager_kwargs)
        m._test_client.evidence_status.return_value = {
            "ok": True, "signal_seq": 0, "evicted": [], "signals": [],
            "hb": {"rounds": 13, "gap_max_ms": 101.5, "rtt_max_ms": 0.4,
                   "late": 0, "interval_ms": 100},
        }
        try:
            drive(m)
        finally:
            m.shutdown()
    finally:
        telemetry.reset_event_log()
    with open(path) as fh:
        return [json.loads(line) for line in fh]


def _latched_error(m):
    m.start_quorum()
    m.wait_quorum()
    m.report_error(RuntimeError("collective died: " + "x" * 300))
    assert not m.should_commit()


def _peer_vote(m):
    m._test_client.should_commit.side_effect = None
    m._test_client.should_commit.return_value = False
    m.start_quorum()
    m.wait_quorum()
    assert not m.should_commit()


def _too_few_replicas(m):
    m.start_quorum()
    m.wait_quorum()
    assert not m.should_commit()


def _failed_heal(m):
    m._test_transport.recv_checkpoint.side_effect = ConnectionResetError("torn")
    m.start_quorum()
    assert not m.should_commit()


def _gate_rpc_timeout(m):
    m._test_client.should_commit.side_effect = TimeoutError("barrier")
    m.start_quorum()
    m.wait_quorum()
    assert not m.should_commit()


def _committed(m):
    m.start_quorum()
    m.wait_quorum()
    assert m.should_commit()


@pytest.mark.parametrize("drive,kwargs,want", [
    (_committed, {}, {"cause": "ok", "local_vote": True}),
    (_latched_error, {}, {"cause": "local_error", "local_vote": False,
                          "error_class": "RuntimeError"}),
    (_peer_vote, {}, {"cause": "peer_voted_no", "local_vote": True}),
    (_too_few_replicas,
     {"min_replica_size": 3,
      "quorum_result": make_quorum_result(replica_world_size=2, max_world_size=2)},
     {"cause": "not_enough_replicas", "local_vote": False}),
    (_failed_heal,
     {"quorum_result": make_quorum_result(
         heal=True, max_step=5, recover_src_replica_rank=1,
         recover_src_manager_address="127.0.0.1:1")},
     {"cause": "healing", "local_vote": False}),
    (_gate_rpc_timeout, {}, {"cause": "local_error", "local_vote": True,
                             "error_class": "TimeoutError"}),
], ids=["ok", "latched-error", "peer-vote", "too-few-replicas", "failed-heal",
        "gate-rpc-failed"])
def test_every_gate_carries_its_cause(tmp_path, monkeypatch, drive, kwargs, want):
    from torchft_tpu.manager import GATE_CAUSES

    events = _journaled(tmp_path, monkeypatch, drive, **kwargs)
    (gate,) = [e["attrs"] for e in events if e["event"] == "commit_gate"]
    assert gate["cause"] in GATE_CAUSES
    assert {k: gate[k] for k in want} == want
    assert gate["committed"] == (want["cause"] == "ok")
    assert ("error" in gate) == ("error_class" in gate) == (
        want["cause"] in ("local_error", "healing"))
    if "error" in gate:
        assert 0 < len(gate["error"]) <= 200
    # Under what it was judged, and how the host stood.
    assert gate["quorum_id"] == 1 and isinstance(gate["participants"], list)
    assert (gate["hb_rounds"], gate["hb_gap_max_ms"], gate["hb_rtt_max_ms"],
            gate["hb_late"]) == (13, 101.5, 0.4, 0)
    assert gate["rss_peak_bytes"] > 2**20
    assert gate["elapsed_s"] >= 0


def test_the_gate_reads_and_resets_the_liveness_counters_once(tmp_path, monkeypatch):
    """One ``evidence_status(reset=True)`` a gate; an eviction the
    lighthouse reports is journaled as ``lh_evicted``; a signal an ack
    showed is journaled once, however often it is handed out."""
    lapse = {"seq": 7, "ts_ms": 1234, "replica_id": "other:u1",
             "source": "hb_lapse", "site": "lighthouse.fleet_scan",
             "detail": {"gap_ms": 1207, "budget_ms": 1200}}
    evicted = {"seq": 8, "ts_ms": 2000, "gap_ms": 1650, "open_gap_ms": 1210,
               "budget_ms": 1200, "out_ms": 440,
               "erased": "heartbeat+participant+quorum_request",
               "via": "heartbeat", "sender_gap_ms": 100.2, "sender_rtt_ms": 1549.0}

    def drive(m):
        status = m._test_client.evidence_status
        for step in range(3):
            base = dict(status.return_value)
            base["signals"] = [lapse]  # handed out again and again
            base["evicted"] = [evicted] if step == 1 else []
            status.return_value = base
            m.start_quorum()
            m.wait_quorum()
            assert m.should_commit()
        assert status.call_count == 3
        for call in status.call_args_list:
            assert call.kwargs["reset"] is True

    events = _journaled(tmp_path, monkeypatch, drive)
    assert len([e for e in events if e["event"] == "commit_gate"]) == 3
    (sig,) = [e for e in events if e["event"] == "failure_signal"]
    assert sig["step"] == 0 and sig["attrs"]["seq"] == 7
    assert (sig["attrs"]["source"], sig["attrs"]["subject"], sig["attrs"]["site"],
            sig["attrs"]["origin"]) == (
        "hb_lapse", "other:u1", "manager.gate", "lighthouse.fleet_scan")
    assert sig["attrs"]["detail"] == {"gap_ms": 1207, "budget_ms": 1200}
    (ev,) = [e for e in events if e["event"] == "lh_evicted"]
    assert ev["step"] == 1 and ev["attrs"] == evicted


def test_a_server_without_the_counters_gives_a_gate_without_them(tmp_path, monkeypatch):
    """An older manager server's ``evidence_status`` has no ``hb``, and
    one that cannot answer raises: the gate is journaled all the same."""
    def drive(m):
        m._test_client.evidence_status.return_value = {"ok": True, "signal_seq": 0}
        m.start_quorum()
        m.wait_quorum()
        assert m.should_commit()
        m._test_client.evidence_status.side_effect = TimeoutError("busy")
        m.start_quorum()
        m.wait_quorum()
        assert m.should_commit()

    events = _journaled(tmp_path, monkeypatch, drive)
    gates = [e["attrs"] for e in events if e["event"] == "commit_gate"]
    assert len(gates) == 2
    for g in gates:
        assert g["cause"] == "ok" and "hb_rounds" not in g and "rss_peak_bytes" in g
