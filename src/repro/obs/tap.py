"""Streaming round taps: ``jax.experimental.io_callback`` emission of the
per-round telemetry dict FROM INSIDE the jitted computation.

Two traced-side helpers cover the two runtimes:

* :func:`emit_in_scan` — inside a ``lax.scan`` body (the simulator's
  ``run_rounds``).  ``ordered=True`` keeps the host callbacks in round
  order, so sinks see round t before round t+1 while the scan is still
  executing later rounds.
* :func:`emit_step` — in the jitted distributed round, AFTER its
  ``shard_map`` (``make_fl_round``).  The round's metrics leave the
  shard_map replicated, so the callback sees the whole logical value and
  runs once per step, on one device: one record per round.  (Inside a
  partial-auto shard_map — the ``model`` axis left Auto — jax refuses
  callbacks outright.)  The ROUND index rides in the payload (tapped
  round fns take a trailing replicated ``step`` scalar): the callback is
  unordered — jax supports ordered effects on one device only — so with
  async dispatch callbacks from consecutive steps may arrive out of
  order, and only a payload stamp numbers records correctly (it also
  makes resumed runs exact: the stamp is the actual step index, not a
  host-side arrival count).

Both are strict no-ops when ``tap is None``: nothing is traced, so the
lowered HLO is byte-identical to a build that never heard of obs (the
zero-cost-off invariant ``tests/test_obs.py`` pins).

The host adapters (:func:`scan_sink_tap` / :func:`step_sink_tap`) turn
a :class:`~repro.obs.sinks.MetricsSink` into the host callable the taps
invoke: each call converts the telemetry pytree (np arrays by the time
it reaches the host) into one versioned record (``sinks.make_record``)
and emits it — the scan adapter numbers rounds by counting its ordered
callbacks, the step adapter reads the payload's round stamp.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from jax.experimental import io_callback

from repro.obs import sinks as _sinks

#: a host callable receiving the telemetry dict (np-converted pytree)
ScanTap = Callable[[Dict[str, Any]], None]
#: a host callable receiving (telemetry dict, round index)
StepTap = Callable[[Dict[str, Any], Any], None]


def emit_in_scan(tel: Dict[str, Any], tap: Optional[ScanTap]) -> None:
    """Stream one round's telemetry from inside a ``lax.scan`` body.

    ``tap=None`` traces NOTHING (zero-cost-off); otherwise an ordered
    ``io_callback`` ships ``tel`` to the host as the scan executes.
    """
    if tap is None:
        return
    io_callback(tap, None, tel, ordered=True)


def emit_step(tel: Dict[str, Any], round_index,
              tap: Optional[StepTap]) -> None:
    """Stream one distributed round's metrics from the jitted step.

    Call it outside the round's ``shard_map``, on its replicated metrics:
    the callback then runs once per step.  ``round_index`` (the tapped
    round fn's trailing replicated ``step`` scalar) stamps the record —
    the callback is unordered, so arrival order cannot number rounds.
    ``tap=None`` traces nothing.
    """
    if tap is None:
        return
    if round_index is None:
        raise ValueError(
            "a tapped distributed round needs its step index: call the "
            "round fn with the trailing `step` scalar so streamed records "
            "carry their true round stamp")
    io_callback(tap, None, tel, round_index, ordered=False)


def scan_sink_tap(sink: "_sinks.MetricsSink", *, kind: str = "fl_round",
                  start_round: int = 0, every: int = 1) -> ScanTap:
    """Host adapter: telemetry dict -> versioned record -> ``sink.emit``.

    Rounds are numbered ``start_round, start_round+1, ...`` in callback
    arrival order (the ordered scan tap guarantees that IS round order).
    ``every`` keeps only every N-th round's record (round index still
    advances every callback, so kept records carry their true round).
    """
    counter = [start_round]

    def tap(tel: Dict[str, Any]) -> None:
        r = counter[0]
        counter[0] += 1
        if (r - start_round) % every:
            return
        sink.emit(_sinks.make_record(kind, r, tel))

    return tap


def step_sink_tap(sink: "_sinks.MetricsSink", *, kind: str = "fl_round",
                  every: int = 1) -> StepTap:
    """Host adapter for the distributed round's tap: record with the
    payload's round stamp.  No host-side counter: the unordered callback
    may deliver consecutive steps out of order, so the record's round is
    the ``round_index`` the traced side shipped (which also keeps a resumed
    run's appended JSONL stream monotonic in true step index).  ``every``
    keeps steps whose ABSOLUTE index is a multiple of ``every``."""

    def tap(tel: Dict[str, Any], round_index) -> None:
        r = int(round_index)
        if r % every:
            return
        sink.emit(_sinks.make_record(kind, r, tel))

    return tap
