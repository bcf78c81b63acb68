"""A per-test time limit without a plugin: SIGALRM in the test's thread."""

import functools
import signal


def limit(seconds: int):
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kw):
            def stop(_sig, _frame):
                raise TimeoutError(f"{fn.__name__} passed its {seconds} s limit")

            old = signal.signal(signal.SIGALRM, stop)
            signal.alarm(seconds)
            try:
                return fn(*args, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        return run
    return wrap
