"""Tier 1's guard of the benchmark's clock rule (PERF.md 3, Load
generator): in a traced window the seconds the profiler takes to start
and to stop are off the runner's clock, so a traced run offers the
traffic an untraced one does.  The test is benchmark/tests/test_clock.py's
own first one, run here as it stands, one case a serving runner
(`lm_serve`, `retention_serve`) at the tiny presets; its other test stays
with the benchmark's suite.  A CPU run gives counts, never a time."""

from benchmark.tests.test_clock import (  # noqa: F401  (collected here)
    root,
    test_the_profiler_is_off_the_clock,
)
