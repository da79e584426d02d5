"""Priority + credit scheduled queue.

Counterpart of reference ``scheduled_queue.{h,cc}``:
  * tasks kept sorted by (priority desc, key asc) — scheduled_queue.cc:78-98;
  * ``get_task`` skips tasks that are not ready (ready-event / ReadyTable
    gates) or whose byte size exceeds the remaining credits, and decrements
    credits on grant — scheduled_queue.cc:100-136;
  * ``report_finish`` returns credits — scheduled_queue.cc:168-174;
  * only the scheduled stage uses credits (the reference enables it only for
    the root's REDUCE queue, scheduled_queue.cc:24-37); an unscheduled queue
    grants unlimited credit.

This Python implementation is the reference semantics for tests and the
fallback when the native C++ engine (byteps_tpu/native) is unavailable; the
eager engine uses whichever is loaded.  Under jit the same ordering rule is
applied *statically* via ``BucketPlan.schedule_order()``: it fixes the order
in which a step's collectives are issued, not whether they are hidden.  On
the v5e the chain of packed buckets ran after the backward pass, all of it
exposed (37.6 ms a step, PERF_LEDGER.jsonl PR 22); what hid communication
was making a collective a leaf's own (12.7 ms exposed, ledger PR 28; see
training/step.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

from . import logging as bps_log
from .types import TensorTaskEntry

UNLIMITED_CREDIT = 34359738368  # 32 GB, reference scheduled_queue.cc:40-42


class ScheduledQueue:
    def __init__(
        self,
        scheduled: bool = False,
        credit_bytes: int = 0,
        ready_check: Optional[Callable[[TensorTaskEntry], bool]] = None,
        name: str = "",
    ):
        self._is_scheduled = scheduled
        self._credits = credit_bytes if scheduled and credit_bytes > 0 else UNLIMITED_CREDIT
        self._initial_credits = self._credits
        self._ready_check = ready_check
        self._queue: List[TensorTaskEntry] = []
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._closed = False
        self.name = name

    def add_task(self, task: TensorTaskEntry) -> None:
        """Insert keeping (priority desc, key asc) order
        (reference scheduled_queue.cc:78-98)."""
        with self._cv:
            lo, hi = 0, len(self._queue)
            k = (-task.priority, task.key)
            while lo < hi:
                mid = (lo + hi) // 2
                mk = (-self._queue[mid].priority, self._queue[mid].key)
                if mk <= k:
                    lo = mid + 1
                else:
                    hi = mid
            self._queue.insert(lo, task)
            bps_log.trace(
                "queue %s: added %s key %d prio %d (%d pending)",
                self.name, task.name, task.key, task.priority, len(self._queue),
            )
            self._cv.notify_all()

    def get_task(self, key: Optional[int] = None) -> Optional[TensorTaskEntry]:
        """Grant the best ready task within the credit budget, or None.

        Mirrors reference scheduled_queue.cc:100-161 (both the scan variant
        and the by-key variant used by signal-driven dequeues).
        """
        with self._cv:
            for i, task in enumerate(self._queue):
                if key is not None and task.key != key:
                    continue
                if self._ready_check is not None and not self._ready_check(task):
                    continue
                if self._is_scheduled and task.length > self._credits:
                    continue
                if self._is_scheduled:
                    self._credits -= task.length
                del self._queue[i]
                bps_log.trace(
                    "queue %s: granted %s key %d (credits left %d)",
                    self.name, task.name, task.key, self._credits,
                )
                return task
            return None

    def wait_task(self, timeout: Optional[float] = None) -> Optional[TensorTaskEntry]:
        """Blocking get — condition-variable driven instead of the
        reference's 1 microsecond poll-sleep (core_loops.cc:130).
        Returns None immediately once the queue is ``close()``d (after
        draining nothing further arrives), so consumer loops need no
        poison task to exit."""
        with self._cv:
            while True:
                task = self._get_locked()
                if task is not None:
                    return task
                if self._closed:
                    return None
                if not self._cv.wait(timeout):
                    return None

    def _get_locked(self) -> Optional[TensorTaskEntry]:
        for i, task in enumerate(self._queue):
            if self._ready_check is not None and not self._ready_check(task):
                continue
            if self._is_scheduled and task.length > self._credits:
                continue
            if self._is_scheduled:
                self._credits -= task.length
            del self._queue[i]
            return task
        return None

    def close(self) -> None:
        """Wake every ``wait_task`` waiter and make future waits return
        None at once.  ``add_task`` after close still enqueues (the task
        will never be granted by ``wait_task`` — callers that must fail
        such tasks loudly ``drain()`` after close); this keeps shutdown
        races benign instead of raising into producer threads."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def drain(self) -> List[TensorTaskEntry]:
        """Remove and return every queued task, ignoring readiness and
        credits (no credit accounting happens — callers use this to
        fail/abandon a queue wholesale, not to execute the tasks)."""
        with self._cv:
            tasks, self._queue = list(self._queue), []
            return tasks

    def report_finish(self, task: TensorTaskEntry) -> None:
        """Return credits (reference scheduled_queue.cc:168-174)."""
        with self._cv:
            if self._is_scheduled:
                self._credits += task.length
            self._cv.notify_all()

    def try_debit(self, n: int) -> bool:
        """Consume ``n`` credits for work granted *outside* the queue —
        the serving engine's prefill continuation chunks share one
        credit pool with its queued admissions (serving/scheduler.py).
        Returns False (and debits nothing) when the remaining credits
        cannot cover ``n``; always True on an unscheduled queue.  Pair
        every successful debit with :meth:`credit`."""
        with self._cv:
            if not self._is_scheduled:
                return True
            if n > self._credits:
                return False
            self._credits -= n
            return True

    def credit(self, n: int) -> None:
        """Return ``n`` directly-debited credits (see :meth:`try_debit`)."""
        with self._cv:
            if self._is_scheduled:
                self._credits += n
                self._cv.notify_all()

    def debit_wait(self, n: int, timeout: float) -> bool:
        """:meth:`try_debit`'s blocking form: wait up to ``timeout``
        seconds for ``n`` credits and consume them — woken by
        :meth:`credit`/:meth:`report_finish` instead of the caller
        polling.  Returns False on timeout or a closed queue."""
        deadline = time.monotonic() + timeout
        with self._cv:
            if not self._is_scheduled:
                return True
            while n > self._credits:
                left = deadline - time.monotonic()
                if left <= 0 or self._closed:
                    return False
                self._cv.wait(left)
            self._credits -= n
            return True

    def remove(self, task: TensorTaskEntry) -> bool:
        """Remove a still-pending task without granting it (eager
        cancellation).  No credit accounting: the task was never
        debited.  False when the task is no longer queued (already
        granted or drained) — the caller falls back to grant-time
        retirement."""
        with self._cv:
            for i, queued in enumerate(self._queue):
                if queued is task:
                    del self._queue[i]
                    return True
            return False

    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def credits(self) -> int:
        with self._lock:
            return self._credits
