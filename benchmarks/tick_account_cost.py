"""What the serving engine's tick account costs a tick, on this host.

    python benchmarks/tick_account_cost.py

Times the account's own calls of one steady decode tick
(``serving/engine.py:_TickAccount``: begin, admitted, the ``engine.book``
annotation, the dispatch and wait parts around nothing, close, book, end
with its ring write) in a loop, first with no profiler session and then
with one recording (``host_tracer_level`` 2, as ``perf/run.py`` traces).
Prints one JSON line: microseconds a tick for each set of runs and their
medians, beside an empty loop's. Run it on the chip's host, where the
engine runs; a CPU box gives that box's numbers.
"""

import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def steady(acct):
    acct.begin()
    acct.admitted(0)
    acct.book()
    with acct.dispatch:
        pass
    with acct.wait:
        pass
    acct.close()
    acct.book()
    acct.lanes, acct.ahead = 24, True
    acct.end()


def empty(acct):
    pass


def per_tick_us(fn, acct, n):
    for _ in range(2000):
        fn(acct)
    t0 = time.perf_counter_ns()
    for _ in range(n):
        fn(acct)
    return (time.perf_counter_ns() - t0) / n / 1e3


def main():
    import jax

    from apex_tpu.serving import engine

    acct = engine._TickAccount(engine.TICK_LOG_TICKS)
    out = {"device": jax.devices()[0].device_kind,
           "loop_us": [per_tick_us(empty, acct, 100_000) for _ in range(7)],
           "off_us": [per_tick_us(steady, acct, 100_000) for _ in range(7)]}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            out["on_us"] = [per_tick_us(steady, acct, 20_000)
                            for _ in range(5)]
        finally:
            jax.profiler.stop_trace()
    for key in ("loop_us", "off_us", "on_us"):
        out[key[:-3] + "_median_us"] = statistics.median(out[key])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
