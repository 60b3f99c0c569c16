"""Retry shim for grpcio's process-global aio poller flake.

Deep into a long test session, grpcio's process-global aio poller
occasionally breaks down with EAGAIN (upstream flake, observed as a
driver run that completes with ZERO successful requests while the
server is demonstrably healthy). The affected call sites (the
genai-perf e2e test, the pod, router and fleet tests) share this one
two-attempt loop. A genuine regression fails every attempt, so the
retry cannot mask one.
"""

import functools
import logging
import warnings
from typing import Callable, TypeVar

T = TypeVar("T")

# Why two: one retry is enough to ride over a single poller breakdown,
# and every extra attempt doubles how long a REAL regression takes to
# fail. No caller has ever needed a third.
DEFAULT_ATTEMPTS = 2


def retry_grpc_poller_flake(
    run: Callable[[], T],
    succeeded: Callable[[T], bool],
    attempts: int = DEFAULT_ATTEMPTS,
) -> T:
    """Run ``run()`` up to ``attempts`` times until ``succeeded(result)``.

    ``run`` performs one full driver pass (it may raise — exceptions
    propagate immediately, only the zero-requests flake signature is
    retried); ``succeeded`` classifies its result. The LAST result is
    returned either way so callers assert on it and fail with the real
    evidence when every attempt came up empty.
    """
    if attempts < 1:
        raise ValueError("attempts must be >= 1")
    result = run()
    for _ in range(attempts - 1):
        if succeeded(result):
            break
        result = run()
    return result


class _PollerBreakdowns(logging.Handler):
    """Counts the records asyncio logs when the poller breaks down:
    ``Exception in callback PollerCompletionQueue._handle_events`` with
    ``BlockingIOError`` (EAGAIN), hundreds at a time."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.seen = 0

    def emit(self, record: logging.LogRecord) -> None:
        raised = record.exc_info[0] if record.exc_info else None
        if (
            raised is not None
            and issubclass(raised, BlockingIOError)
            and "PollerCompletionQueue._handle_events" in record.getMessage()
        ):
            self.seen += 1


def rerun_on_grpc_poller_breakdown(test: Callable[..., T]) -> Callable[..., T]:
    """Decorator for a test that drives ``grpc.aio`` clients and servers
    on several event loops of one process: run it again, once, when it
    failed AND the poller broke down while it ran.

    A breakdown stalls every aio call of the process for its length, so
    a healthy replica answers late (outlier-ejected, or the call fails
    INTERNAL in grpc core) with nothing wrong in the code under test:
    in 20 runs of ``tests/test_router.py`` + ``test_fleet.py`` under
    six workers the two runs that failed were the two that logged it
    (1,064 and 715 records) and the 18 that passed logged none. A
    failure WITHOUT the records is raised at once, and a regression
    fails the second attempt too."""

    @functools.wraps(test)
    def wrapper(*args, **kwargs):
        asyncio_log = logging.getLogger("asyncio")
        for attempt in range(DEFAULT_ATTEMPTS):
            breakdowns = _PollerBreakdowns()
            asyncio_log.addHandler(breakdowns)
            try:
                return test(*args, **kwargs)
            except Exception:
                if not breakdowns.seen or attempt == DEFAULT_ATTEMPTS - 1:
                    raise
                warnings.warn(
                    f"{test.__name__} failed while grpcio's aio poller "
                    f"broke down ({breakdowns.seen} records); running it "
                    "again, once"
                )
            finally:
                asyncio_log.removeHandler(breakdowns)

    return wrapper
