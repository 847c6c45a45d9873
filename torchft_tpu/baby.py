"""Subprocess-isolated process group ("Baby PG").

Runs the real collective backend (:class:`ProcessGroupSocket`) in a spawned
child process so a wedged or crashed collective layer can be SIGKILLed and
respawned without taking down the trainer — the capability of the
reference's ``ProcessGroupBaby*`` family (reference: process_group.py
1241-1798), rebuilt for the TPU replica axis:

- the parent never blocks on the child: ops are issued over a command pipe
  and resolved by a future-handler thread reading a result pipe, so
  ``wait(timeout)`` is always interruptible;
- in-place collectives (allreduce, broadcast) move payloads through POSIX
  shared memory, written through by the child — no pickling of gradient
  buffers on the hot path (the analog of the reference's
  ``_maybe_share_tensors``, process_group.py:1310-1321);
- ``configure`` kills (SIGKILL) and respawns the child (reference:
  process_group.py:1386-1431), ``abort`` kills it and fails all in-flight
  work, and a child death detected on the pipe fails pending work instead
  of wedging the trainer;
- ``num_active_work`` introspection (reference: process_group.py:1790-1795).

The trainer process stays alive through any of: child crash, child wedge
(killed via ``abort`` after a ``wait`` timeout), or peer death surfacing as
a collective error in the child.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import threading
import time
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.process_group import (
    ProcessGroup,
    ReduceOp,
    _as_list,
    _per_rank_lists,
)
from torchft_tpu.work import ErrorWork, Work

import logging

logger = logging.getLogger(__name__)

# Arrays at or above this size ride shared memory; smaller ones are pickled
# through the pipe (a 4 KiB control tensor isn't worth an shm segment).
_SHM_THRESHOLD = 1 << 16


def _release_shms(shms: List[shared_memory.SharedMemory]) -> None:
    """Close + unlink, tolerating segments already gone (a dying child's
    resource tracker can unlink first)."""
    for shm in shms:
        try:
            shm.close()
        except OSError:
            pass
        try:
            shm.unlink()
        except (OSError, FileNotFoundError):
            pass


def _encode_arrays(
    arrays: List[np.ndarray], shms: List[shared_memory.SharedMemory]
) -> List[Tuple]:
    """Parent-side: stage arrays for the child. Large arrays are copied into
    fresh shm segments (appended to ``shms``); small ones inlined."""
    meta: List[Tuple] = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        if a.nbytes >= _SHM_THRESHOLD:
            shm = shared_memory.SharedMemory(create=True, size=a.nbytes)
            np.ndarray(a.shape, a.dtype, buffer=shm.buf)[...] = a
            shms.append(shm)
            meta.append(("shm", shm.name, str(a.dtype), a.shape))
        else:
            meta.append(("inline", a.tobytes(), str(a.dtype), a.shape))
    return meta


def _decode_arrays(
    meta: List[Tuple], shms: List[shared_memory.SharedMemory]
) -> List[np.ndarray]:
    """Child-side: reconstruct arrays. shm-backed ones write through."""
    out: List[np.ndarray] = []
    for kind, payload, dtype, shape in meta:
        if kind == "shm":
            shm = shared_memory.SharedMemory(name=payload)
            # The parent owns these segments' lifetime. On Python <= 3.12
            # attaching registers with THIS process's resource tracker,
            # which would unlink them when the child exits/dies — racing
            # the parent's own cleanup. Unregister to disown.
            try:
                from multiprocessing import resource_tracker

                resource_tracker.unregister(shm._name, "shared_memory")
            except Exception:  # noqa: BLE001 - tracker API is private-ish
                pass
            shms.append(shm)
            out.append(np.ndarray(shape, np.dtype(dtype), buffer=shm.buf))
        else:
            out.append(
                np.frombuffer(bytearray(payload), dtype=np.dtype(dtype)).reshape(
                    shape
                )
            )
    return out


def _baby_worker(
    cmd_conn, res_conn, store_addr: str, rank: int, world_size: int,
    timeout: float,
) -> None:
    """Child main: configure a real socket PG, then replay ops from the
    command pipe in issue order (reference worker loop:
    process_group.py:1441-1605). Runs until "exit" or SIGKILL."""
    from torchft_tpu.process_group import make_process_group

    # Factory, not a hardcoded class: TORCHFT_PG is inherited across the
    # process boundary, so baby groups ride the same backend as the parent.
    pg = make_process_group(timeout=timeout)
    try:
        pg.configure(store_addr, rank, world_size)
    except Exception as e:  # noqa: BLE001 - parent maps this to configure fail
        res_conn.send(("boot_error", repr(e)))
        return
    res_conn.send(("ready",))

    open_shms: List[shared_memory.SharedMemory] = []
    try:
        while True:
            try:
                msg = cmd_conn.recv()
            except (EOFError, OSError):
                break
            kind = msg[0]
            if kind == "exit":
                break
            if kind == "set_timeout":
                timeout = float(msg[1])
                pg.set_timeout(timeout)
                continue
            if kind == "stall":
                # Test-only wedge injection: simulates a hung collective
                # layer (the scenario Baby PG exists for).
                time.sleep(msg[1])
                continue
            assert kind == "func", kind
            _, op_id, name, arg_meta, kwargs = msg
            del open_shms[:]
            try:
                arrays = _decode_arrays(arg_meta, open_shms)
                result = _run_op(pg, name, arrays, kwargs, timeout)
                # In-place ops already wrote through shm; anything inlined
                # (or op-produced) goes back over the pipe.
                res_conn.send(("done", op_id, _pickle_result(name, result, arrays, arg_meta)))
            except Exception as e:  # noqa: BLE001 - report, keep serving
                res_conn.send(("error", op_id, repr(e)))
            finally:
                for shm in open_shms:
                    shm.close()
                del open_shms[:]
    finally:
        pg.shutdown()
        try:
            res_conn.close()
        except OSError:
            pass


def _run_op(pg, name: str, arrays, kwargs: Dict[str, Any], timeout: float):
    if name == "allreduce":
        return pg.allreduce(arrays, ReduceOp(kwargs["op"])).wait(timeout)
    if name == "allgather":
        return pg.allgather(arrays).wait(timeout)
    if name == "broadcast":
        return pg.broadcast(arrays, root=kwargs["root"]).wait(timeout)
    if name == "reduce_scatter":
        return pg.reduce_scatter(arrays, ReduceOp(kwargs["op"])).wait(timeout)
    if name == "alltoall":
        parts = kwargs["parts"]
        if parts:  # a list of arrays a rank, flattened for the pipe
            arrays = [
                arrays[i : i + parts] for i in range(0, len(arrays), parts)
            ]
        return pg.alltoall(arrays).wait(timeout)
    if name == "barrier":
        return pg.barrier().wait(timeout)
    if name == "send":
        return pg.send(arrays, dst=kwargs["dst"], tag=kwargs["tag"]).wait(timeout)
    if name == "recv":
        return pg.recv(
            src=kwargs["src"], tag=kwargs["tag"],
            num_tensors=kwargs["num_tensors"],
        ).wait(timeout)
    raise ValueError(f"unknown op {name!r}")


def _pickle_result(name, result, arrays, arg_meta):
    """Results for in-place ops whose inputs rode shm need no payload: the
    child already wrote through. Everything else is pickled."""
    if name in ("allreduce", "broadcast"):
        # Write back any *inlined* inputs (too small for shm) explicitly.
        inline_payloads = [
            a.tobytes() if m[0] == "inline" else None
            for a, m in zip(arrays, arg_meta)
        ]
        return ("inplace", inline_payloads)
    return ("value", pickle.dumps(result, protocol=pickle.HIGHEST_PROTOCOL))


class _BabyWork(Work):
    """Parent-side handle; resolved by the future-handler thread."""

    def __init__(self, op_id: int) -> None:
        self._op_id = op_id
        self._event = threading.Event()
        self._result: Any = None
        self._exc: Optional[BaseException] = None
        self._callbacks: List[Any] = []
        self._cb_lock = threading.Lock()

    def _complete(self, result: Any = None, exc: Optional[BaseException] = None):
        with self._cb_lock:
            if self._event.is_set():
                return  # first completion wins (e.g. abort vs late result)
            self._result = result
            self._exc = exc
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            try:
                fn(self)
            except Exception:  # noqa: BLE001
                logger.exception("baby work callback failed")

    def wait(self, timeout: Optional[float] = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"baby pg op {self._op_id} timed out after {timeout}s "
                "(child may be wedged: call abort() to kill it)"
            )
        if self._exc is not None:
            raise self._exc
        return self._result

    def done(self) -> bool:
        return self._event.is_set()

    def exception(self) -> Optional[BaseException]:
        return self._exc if self._event.is_set() else None

    def add_done_callback(self, fn) -> None:
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)


class ProcessGroupBabySocket(ProcessGroup):
    """Socket process group running in a kill-safe subprocess.

    Drop-in for :class:`ProcessGroupSocket` wherever the ``ProcessGroup``
    ABC is accepted (Manager, DDP, transports). The reference equivalent is
    ``ProcessGroupBabyGloo`` (process_group.py:1853-1899).
    """

    def __init__(self, timeout: float = 60.0) -> None:
        self._timeout = timeout
        self._rank = -1
        self._world = 0
        self._child: Optional[mp.process.BaseProcess] = None
        self._cmd_conn = None
        self._res_conn = None
        self._handler: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # Serializes issue order (op-id allocation -> pipe send) WITHOUT
        # blocking abort(): a full cmd pipe under a wedged child blocks the
        # sender on this lock only, so abort() can still take self._lock,
        # SIGKILL the child, and break the pipe out from under the send.
        self._send_lock = threading.Lock()
        self._errored: Optional[Exception] = None
        self._next_op = 0
        self._pending: Dict[int, Tuple[_BabyWork, List, List]] = {}
        self._generation = 0

    # -- lifecycle ---------------------------------------------------------

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        failed: List[Tuple[_BabyWork, Exception]] = []
        try:
            self._configure_inner(store_addr, rank, world_size, failed)
        finally:
            for work, err in failed:
                work._complete(exc=err)

    def _configure_inner(
        self, store_addr: str, rank: int, world_size: int, failed: List
    ) -> None:
        with self._lock:
            failed.extend(self._kill_child_locked())
            self._errored = None
            self._rank = rank
            self._world = world_size
            self._generation += 1
            generation = self._generation

        # Spawn + ready-wait OUTSIDE the lock: both can take seconds (fresh
        # interpreter + rendezvous), and abort() must be able to interrupt a
        # wedged reconfigure (the Manager arms a context_timeout around
        # pg.configure for exactly that).
        ctx = mp.get_context("spawn")
        parent_cmd, child_cmd = ctx.Pipe()
        parent_res, child_res = ctx.Pipe(duplex=False)
        proc = ctx.Process(
            target=_baby_worker,
            args=(
                child_cmd, child_res, store_addr, rank, world_size,
                self._timeout,
            ),
            daemon=True,
            name=f"baby-pg-{rank}",
        )
        proc.start()
        child_cmd.close()
        child_res.close()
        try:
            deadline = time.monotonic() + self._timeout + 30.0
            ready = False
            while time.monotonic() < deadline:
                # Short poll slices so an abort() (which latches _errored)
                # cancels the wait promptly.
                if parent_res.poll(0.2):
                    ready = True
                    break
                with self._lock:
                    if self._errored is not None or self._generation != generation:
                        raise RuntimeError(
                            "baby pg aborted/reconfigured during configure"
                        )
            if not ready:
                raise RuntimeError(
                    f"baby pg rank {rank}: child did not become ready"
                )
            try:
                msg = parent_res.recv()
            except (EOFError, OSError) as e:
                raise RuntimeError(
                    f"baby pg rank {rank}: child died during boot "
                    f"(before reporting ready): {e!r}"
                ) from e
            if msg[0] != "ready":
                raise RuntimeError(
                    f"baby pg rank {rank}: child failed to configure: {msg[1]}"
                )
            with self._lock:
                if self._errored is not None or self._generation != generation:
                    raise RuntimeError(
                        "baby pg aborted/reconfigured during configure"
                    )
                self._child = proc
                self._cmd_conn = parent_cmd
                self._res_conn = parent_res
                handler = threading.Thread(
                    target=self._future_handler,
                    args=(parent_res, generation),
                    name=f"baby-pg-futures-{rank}",
                    daemon=True,
                )
                self._handler = handler
                handler.start()
        except Exception:
            proc.kill()
            proc.join(timeout=10.0)
            for conn in (parent_cmd, parent_res):
                try:
                    conn.close()
                except OSError:
                    pass
            raise

    def _future_handler(self, res_conn, generation: int) -> None:
        """Drains the child's result pipe, resolving works (reference:
        _future_handler thread, process_group.py:1539-1605). Child death
        (pipe EOF) fails everything pending."""
        while True:
            try:
                msg = res_conn.recv()
            except (EOFError, OSError):
                with self._lock:
                    if self._generation != generation:
                        return  # superseded by a reconfigure
                    err = self._errored or RuntimeError(
                        "baby pg child process died"
                    )
                    self._errored = err
                    pending = list(self._pending.values())
                    self._pending.clear()
                for work, _, shms in pending:
                    _release_shms(shms)
                    work._complete(exc=err)
                return
            kind, op_id = msg[0], msg[1]
            with self._lock:
                entry = self._pending.pop(op_id, None)
            if entry is None:
                continue
            work, arrays, shms = entry
            # Any failure resolving THIS op must not kill the handler
            # thread — every later op would then hang to timeout.
            exc: Optional[BaseException] = None
            result = None
            if kind == "error":
                exc = RuntimeError(f"baby pg op failed in child: {msg[2]}")
            else:
                try:
                    result = self._decode_result(msg[2], arrays, shms)
                except Exception as e:  # noqa: BLE001 - e.g. read-only input
                    exc = e
            _release_shms(shms)
            work._complete(result=result, exc=exc)

    def _decode_result(self, payload, arrays: List[np.ndarray], shms) -> Any:
        kind, body = payload
        if kind == "inplace":
            # shm-staged inputs: copy the child's reduced bytes back into
            # the caller's arrays; inlined ones come back over the pipe.
            shm_i = 0
            for a, inline in zip(arrays, body):
                if inline is None:
                    shm = shms[shm_i]
                    shm_i += 1
                    a[...] = np.ndarray(a.shape, a.dtype, buffer=shm.buf)
                else:
                    a[...] = np.frombuffer(inline, dtype=a.dtype).reshape(
                        a.shape
                    )
            return arrays
        return pickle.loads(body)

    def _kill_child_locked(self) -> List[Tuple[_BabyWork, Exception]]:
        """Kills the child and collects pending works; the CALLER must
        complete them after releasing the lock (completion runs user
        callbacks, which may re-enter this pg)."""
        # Supersede the future-handler generation FIRST: the pipe EOF the
        # kill produces must read as intentional teardown, not latch a
        # phantom "child died" error after a clean shutdown/reconfigure.
        self._generation += 1
        self._drop_wire_scratch()
        if self._child is not None:
            self._child.kill()
            self._child.join(timeout=10.0)
            self._child = None
        for conn in (self._cmd_conn, self._res_conn):
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
        self._cmd_conn = self._res_conn = None
        pending = list(self._pending.values())
        self._pending.clear()
        err = self._errored or RuntimeError("baby pg child killed")
        failed = []
        for work, _, shms in pending:
            _release_shms(shms)
            failed.append((work, err))
        return failed

    def abort(self) -> None:
        with self._lock:
            if self._errored is None:
                self._errored = RuntimeError("baby pg aborted")
            failed = self._kill_child_locked()
        for work, err in failed:
            work._complete(exc=err)

    def shutdown(self) -> None:
        # Politely ask the child to exit, serialized against in-flight
        # func sends (_send_lock, same order as _issue) — but with a
        # BOUNDED wait: a wedged child can leave _issue blocked mid-send
        # holding _send_lock forever, and shutdown must still reach the
        # kill below (the hang-wedge domain this class exists for).  If
        # the lock can't be had, skip the polite exit; the kill makes the
        # interleaving question moot.
        polite = self._send_lock.acquire(timeout=1.0)
        try:
            if polite:
                with self._lock:
                    if self._cmd_conn is not None:
                        try:
                            self._cmd_conn.send(("exit",))
                        except (OSError, BrokenPipeError):
                            pass
        finally:
            if polite:
                self._send_lock.release()
        with self._lock:
            if polite and self._child is not None:
                self._child.join(timeout=5.0)
            failed = self._kill_child_locked()
        for work, err in failed:
            work._complete(exc=err)

    def errored(self) -> Optional[Exception]:
        return self._errored

    def set_timeout(self, timeout: float) -> None:
        self._timeout = timeout
        # Forward to the live child so its op waits and socket deadlines
        # update immediately (not only after the next configure).
        # _send_lock serializes against _issue's func sends: Connection is
        # not thread-safe, and a near-64KiB inline payload is written in
        # multiple syscalls, so an unserialized send here could interleave
        # and corrupt the child's command stream.  Lock order matches
        # _issue: _send_lock, then _lock.
        with self._send_lock, self._lock:
            if self._cmd_conn is not None:
                try:
                    self._cmd_conn.send(("set_timeout", float(timeout)))
                except (OSError, BrokenPipeError, ValueError):
                    pass  # dead child: next configure applies it anyway

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    def getBackendName(self) -> str:
        return "torchft-baby-socket"

    def num_active_work(self) -> int:
        """In-flight op count (reference: process_group.py:1790-1795)."""
        with self._lock:
            return len(self._pending)

    # -- test hooks --------------------------------------------------------

    def _inject_stall(self, seconds: float = 3600.0) -> None:
        """Makes the child sleep before its next op — a deterministic wedge
        for resiliency tests (the scenario this class exists to survive)."""
        # Same cmd-pipe serialization + lock order as set_timeout.
        with self._send_lock, self._lock:
            if self._cmd_conn is None:
                raise RuntimeError("not configured")
            self._cmd_conn.send(("stall", seconds))

    def child_pid(self) -> Optional[int]:
        with self._lock:
            return self._child.pid if self._child is not None else None

    # -- op issue ----------------------------------------------------------

    def _issue(self, name: str, arrays: List[np.ndarray], **kwargs) -> Work:
        with self._send_lock:
            with self._lock:
                if self._errored is not None:
                    return ErrorWork(self._errored)
                conn = self._cmd_conn
                if conn is None:
                    return ErrorWork(RuntimeError("baby pg not configured"))
                op_id = self._next_op
                self._next_op += 1
            # Staging (shm alloc + memcpy) and the pipe send happen OUTSIDE
            # self._lock: both can block, and abort() must stay reachable.
            shms: List[shared_memory.SharedMemory] = []
            try:
                meta = _encode_arrays(arrays, shms)
            except Exception as e:  # noqa: BLE001 - e.g. /dev/shm exhausted
                _release_shms(shms)
                return ErrorWork(e)
            work = _BabyWork(op_id)
            with self._lock:
                if self._errored is not None or self._cmd_conn is not conn:
                    _release_shms(shms)  # aborted/reconfigured meanwhile
                    return ErrorWork(
                        self._errored or RuntimeError("baby pg reconfigured")
                    )
                self._pending[op_id] = (work, arrays, shms)
            try:
                conn.send(("func", op_id, name, meta, kwargs))
            except (OSError, BrokenPipeError, ValueError) as e:
                with self._lock:
                    entry = self._pending.pop(op_id, None)
                    err = self._errored = self._errored or RuntimeError(
                        f"baby pg child pipe broken: {e}"
                    )
                if entry is not None:
                    _release_shms(shms)
                return ErrorWork(err)
            return work

    # -- collectives -------------------------------------------------------

    def allreduce(self, tensors: Any, op: ReduceOp = ReduceOp.SUM) -> Work:
        return self._issue("allreduce", _as_list(tensors), op=op.value)

    def allgather(self, tensors: Any) -> Work:
        return self._issue("allgather", _as_list(tensors))

    def broadcast(self, tensors: Any, root: int = 0) -> Work:
        return self._issue("broadcast", _as_list(tensors), root=root)

    def reduce_scatter(
        self, inputs: Sequence[Any], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        return self._issue("reduce_scatter", _as_list(inputs), op=op.value)

    def alltoall(self, inputs: Sequence[Any]) -> Work:
        nested, per_rank = _per_rank_lists(inputs)
        parts = len(per_rank[0]) if nested and per_rank else 0
        if any(len(p) != parts for p in per_rank if nested):
            return ErrorWork(
                ValueError(
                    "alltoall needs as many arrays for every rank, got "
                    f"{[len(p) for p in per_rank]}"
                )
            )
        flat = [a for p in per_rank for a in p]
        return self._issue("alltoall", flat, parts=parts)

    def barrier(self) -> Work:
        return self._issue("barrier", [])

    def send(self, tensors: Any, dst: int, tag: str = "") -> Work:
        return self._issue("send", _as_list(tensors), dst=dst, tag=tag)

    def recv(self, src: int, tag: str = "", num_tensors: int = 1) -> Work:
        return self._issue(
            "recv", [], src=src, tag=tag, num_tensors=num_tensors
        )
