"""A time limit per test, without a plugin: SIGALRM raises in the test
after `seconds` (main thread of the test process only, where pytest and
its xdist workers run tests; elsewhere the test runs unlimited)."""

import functools
import signal
import threading


def time_limit(seconds):
    def deco(fn):
        @functools.wraps(fn)
        def limited(*args, **kwargs):
            if threading.current_thread() is not threading.main_thread():
                return fn(*args, **kwargs)

            def on_alarm(_sig, _frame):
                raise TimeoutError(f"{fn.__name__} ran past its "
                                   f"{seconds} s limit")
            old = signal.signal(signal.SIGALRM, on_alarm)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)
        return limited
    return deco
