"""99th percentile, over the window's ticks only, of how late the broker's
event loop ran a timer that was due: the host profiler's lag sampler
(``rmqtt_tpu/broker/hostprof.py``) sleeps 50 ms and records how far past its
due instant it woke, so a tick waits for whatever the loop thread was doing
when it fell due — a collector pass, the 5 s stats fold, a compile that holds
the GIL. From the deltas of the sampler's cumulative log2 buckets
``hist_host_loop_lag_b<i>``: the upper edge of the bucket that holds the
percentile, exact to a factor of 2. Absent on a broker without the buckets (a
program from before they were exported) or with the host profiler off."""

from _stages import p99_ms

SPEC = {"layer": "broker event loop (one Python thread)", "unit": "ms",
        "source": "program_counter", "moves": "puback_p99_ms"}


def read(run: dict):
    return p99_ms(run, "host.loop_lag")
