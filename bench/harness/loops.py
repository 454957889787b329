"""The closed arrival loop around the served pool.

``LiveExecutor.run`` returns when the scheduler has nothing left, so the
loop feeds requests from the step-function seam (``ServedPool.after_step``).
``watch`` is called after every step (the window uses it to close itself).
"""
from __future__ import annotations

from typing import Callable

from .served import ServedPool, wait_until


def _quiet(_n_finishing: int) -> None:
    pass


def closed_burst(pool: ServedPool, n: int) -> None:
    """Submit ``n`` requests at once and serve them all (warm-up)."""
    now = pool.clock()
    for _ in range(n):
        pool.submit(now)
    pool.after_step = _quiet
    pool.run()


def closed_window(pool: ServedPool, outstanding: int, t0: float,
                  t_stop: float, watch: Callable[[], None]) -> None:
    """Closed loop: ``outstanding`` requests in the system from ``t0``; each
    completion is replaced at once until ``t_stop``, then the pool drains."""
    wait_until(pool.clock, t0)
    for _ in range(outstanding):
        pool.submit(t0)

    def refill(n_finishing: int) -> None:
        now = pool.clock()
        if now < t_stop:
            for _ in range(n_finishing):
                pool.submit(now)
        watch()

    pool.after_step = refill
    pool.run()
    pool.after_step = _quiet
