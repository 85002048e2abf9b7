"""Game-day SLO harness — client-side truth for production claims.

Every number in PERF.md before this package was a server-side
microbenchmark run in isolation. A *game day* is the opposite: a
deterministic, replayable production-traffic scenario — open-loop load
with realistic shapes (diurnal ramp, flash crowd, heavy-tail request
sizes, tenant skew) composed with control-plane failures (the chaos
engine's seeded schedules, rolling updates, scale actions) — whose
verdict is computed purely from what *clients* observed, then
cross-checked against what the server-side observability plane
(replica ledgers, serve metrics, the state engine's task table,
Prometheus gauges) claims happened. Disagreement is a failure: the
observability plane itself is the thing under test.

Layers (docs/GAMEDAY.md):

* ``loadgen``   — seeded open-loop arrival schedules + the runner that
                  fires them at their scheduled instants (coordinated
                  omission cannot hide stalls: latency is measured from
                  the *intended* arrival, not the actual send).
* ``slo``       — client-side accounting: per-phase log-bucketed
                  latency histograms (p50/p99/p99.9), the
                  admitted/shed/failed ledger, error-budget burn.
* ``scenario``  — the replayable spec: load phases + timed actions +
                  the chaos schedule, all a pure function of
                  (scenario, seed): same seed ⇒ same schedule.
* ``runner``    — deploys the workload, drives the scenario end to
                  end, collects every server-side view.
* ``reconcile`` — the outside-in pass joining client and server views
                  per request id.
* ``store``     — last-report storage in the GCS KV (the dashboard's
                  game-day panel and the ``ray_tpu_slo_*`` gauges read
                  it).

Entry points: ``ray-tpu gameday run <scenario>`` and the tier-1
flagship gate in ``tests/test_gameday.py``.
"""

from ray_tpu.gameday.loadgen import (Arrival, ArrivalSchedule,  # noqa: F401
                                     OpenLoopRunner, RequestRecord,
                                     build_schedule)
from ray_tpu.gameday.reconcile import reconcile  # noqa: F401
from ray_tpu.gameday.runner import GameDayResult, run_scenario  # noqa: F401
from ray_tpu.gameday.scenario import (Scenario, builtin_scenarios,  # noqa: F401
                                      chaos_config, load_scenario)
from ray_tpu.gameday.slo import (LatencyHistogram, build_report,  # noqa: F401
                                 error_budget_burn)
from ray_tpu.gameday.store import load_report, publish_report  # noqa: F401
