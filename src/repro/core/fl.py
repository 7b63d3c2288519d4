"""Federated training orchestration (paper Algorithm 1).

Two runtimes share the same math:

* ``FLSimulator`` — the paper's N=100-device MNIST setting: explicit client
  sampling, I local QAT-SGD steps per client (eq. 4, STE fake-quant), uplink
  delta quantization, Bernoulli packet drops, error-aware aggregation
  (eq. 6), and per-round energy/latency from the §II-D model.  vmap over the
  K selected clients; runs on one CPU device.  The hot path is
  ``run_rounds`` — one jitted ``lax.scan`` over rounds (telemetry stacked,
  identical PRNG chain to looping ``run_round``) — which ``train`` and the
  multi-round benchmarks ride.

* ``make_fl_round`` — the production mapping: one client cohort per
  (``pod``, ``data``) mesh shard, model tensor-parallel over ``model``
  (GSPMD auto axes inside a partial-auto ``shard_map``).  Each cohort
  runs I local SGD steps, quantizes its delta, survives with prob. 1−q, and
  the cohorts aggregate with a psum whose WIRE FORMAT is selectable —
  ``collective=`` or ``QuantConfig.wire_format``:

    "paper"/"f32"  f32 psum.  32 wire bits/param; the paper's n-bit uplink
                   payload (§II-D2 ``payload_bits`` = d·n) is simulated in
                   the energy model but not realised on the wire.
    "int"          integer codes in the smallest int container that holds
                   the shard sum (int8/16/32) — 8-32 wire bits/param.
    "packed"       codes bit-packed into dense uint32 words with
                   ceil(log2(K)) guard bits per lane so ONE u32 psum sums
                   every lane carry-free — 32/⌊32/(n+⌈log2 K⌉)⌋ wire
                   bits/param, e.g. 10.7 at n=8, K=2.  This makes the HLO
                   collective bytes track the paper's payload-bits
                   accounting (the energy model's d·n) instead of
                   overshooting it 2-4x.
    "ring"         guard bits gone: the code tree circulates the cohort
                   ring (``lax.ppermute``) packed at the NATIVE n-bit
                   lane; each hop accumulates into an int32 register
                   tree, so the wire is the paper's d·n floor per hop —
                   e.g. 8 bits/param at n=8, K=2 (0.75x "packed") — but
                   the cost grows with K−1 full-vector hops.
    "rsag"         true reduce-scatter + all-gather: one 1/K chunk per
                   hop at a GROWING lane width (hop h carries partial
                   sums of h codes in n+⌈log2 h⌉-bit lanes), finished
                   chunks redistributed at the final n+⌈log2 K⌉ lane —
                   ~2·(n+⌈log2 K⌉) bits/param regardless of K, the
                   large-K cap the per-hop ring lacks (28.5 vs the
                   ring's 120 bits/param at n=8, K=16).
    "auto"         resolved at trace time to the byte-minimal concrete
                   mode for the current (bits, cohort axis sizes) via
                   ``aggregation.resolve_auto`` — ring on the 2x4 debug
                   mesh (8 bits/param), packed on the 16x16 production
                   mesh (16 bits/param).

  Every quantized mode produces the bit-identical aggregated model (same
  codes, same exact integer sum).  The round metrics carry
  ``wire_bits_per_param`` — the bits that actually hit the wire after
  "auto" resolution and degenerate fallbacks (see
  ``aggregation.make_wire_plan`` / ``effective_wire_format``) — so energy
  accounting charges what was really sent, per phase via
  ``aggregation.wire_phase_bits_per_param``.

  The hop modes (ring/rsag) run a double-buffered schedule by default
  (``QuantConfig.pipeline_hops``): hop h+1's ``ppermute`` is issued
  before hop h's accumulate, and under ``use_pallas`` the quantize→pack→
  chunk front-end fuses into one megakernel — bit-identical to the
  sequential schedule, measurably faster wall-clock (d = 421 642,
  bits = 8, CPU interpret; BENCH_collective_modes.json, trends portable):

    mode    K=2 pipelined (vs sequential)   K=16 pipelined (vs sequential)
    ring    ~21 ms (1.64x faster)           ~1188 ms (1.02x)
    rsag    ~19 ms (1.52x)                  ~200 ms (1.18x)
    packed  ~25 ms (0.94x — hop-free, knob inert by design)

  See ``aggregation.py`` for the WirePlan abstraction the six modes hang
  off and ``quantization.pack_codes`` / ``kernels/pack.py`` for the wire
  formats.

Both runtimes accept a **fleet** (``config.fleet.size > 0``): the device
population of ``repro.population`` — per-device pathloss classes,
Gauss-Markov AR(1) correlated fading carried across rounds, batteries
debited by the §II-D energy model (plus the opt-in harvesting credit),
availability, jit-able cohort selection and FBL-tied packet errors, and
PER-DEVICE adaptive uplink power: each round the configured
``PowerConfig.policy`` (fixed / channel_inversion / fbl_target /
lyapunov — ``population.power``) assigns the whole fleet a transmit-power
vector from its current fading/battery state; rates, round costs and
battery debits price that vector and the round telemetry carries its
quantiles next to budget-vs-realized energy and outage-vs-target.  The
simulator threads the ``FleetState`` (plus the single split-per-round
PRNG key) through its ``lax.scan`` carry — the whole 10^6-device update
stays inside the jitted scan; the distributed round threads it through
the step signature (params, batch, rng, fleet) -> (params, metrics,
fleet), replicated.  The power vector, like the battery debit, is a pure
function of (state, config) pricing the mode-independent d·n payload, so
the fleet/power trajectory — and through it the aggregated model — is
bit-identical across every collective wire format.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import base as base_mod
from repro.config.base import Config
from repro.core import aggregation as agg
from repro.core import channel as ch
from repro.core import energy as energy_mod
from repro.core import quantization as quant
from repro.obs import sinks as obs_sinks
from repro.obs import tap as obs_tap
from repro.obs import trace as obs_trace
from repro.population import errors as pop_errors
from repro.population import fleet as pop_fleet
from repro.population import power as pop_power
from repro.population import telemetry as pop_tel
from repro.utils import compat

PyTree = Any

#: fold_in tag deriving the fleet scan-carry key stream from the caller's rng
_FLEET_STREAM = 0xF1EE7


# ---------------------------------------------------------------------------
# paper-faithful simulator (MNIST QNN, N devices, K per round)
# ---------------------------------------------------------------------------

@dataclass
class RoundTelemetry:
    loss: float
    accuracy: float
    survivors: int
    energy_j: float
    tau_s: float


class FLSimulator:
    """Algorithm 1 over an explicit client store."""

    def __init__(self, model, config: Config, client_store, *,
                 macs_per_iter: Optional[float] = None):
        self.model = model
        self.config = config
        self.store = client_store
        self.alphas = jnp.asarray(client_store.client_weights(), jnp.float32)
        self.num_params = int(sum(
            np.prod(s.shape) for s in jax.tree_util.tree_leaves(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)))))
        self.macs = macs_per_iter or config.energy.macs_per_iteration
        # the batched input path: a (num_clients, L) partition table exists
        # only where every partition has one length L, because a compiled
        # draw without replacement needs L static; a ragged store keeps the
        # per-draw path of _round_inputs (see _chunk_inputs)
        sizes = {len(p) for p in client_store.partitions}
        self._part_table = (
            jnp.asarray(np.stack(client_store.partitions), jnp.int32)
            if len(sizes) == 1 else None)
        self._chunk_inputs_fn = jax.jit(self._draw_chunk,
                                        static_argnames="rounds")
        self._round_fn = jax.jit(self._round)
        self._scan_fns: Dict[Any, Callable] = {}
        self._fleet_scan_fns: Dict[Any, Callable] = {}
        # the CURRENT streaming tap (host callable) the compiled scans
        # dispatch through — indirection so one tapped compile serves any
        # sink across run_rounds calls; None while no tap is active
        self._active_tap: Optional[Callable] = None
        # stateful heterogeneous population (None => the paper's homogeneous
        # i.i.d. cohort).  The state persists ACROSS run_rounds calls so
        # chunked train() keeps draining the same batteries / fading chain.
        self.fleet_state: Optional[pop_fleet.FleetState] = None
        if config.fleet.enabled:
            if config.fleet.size < config.fl.devices_per_round:
                raise ValueError(
                    f"fleet.size={config.fleet.size} smaller than the "
                    f"cohort devices_per_round={config.fl.devices_per_round}")
            self.fleet_state = pop_fleet.init_fleet(
                jax.random.PRNGKey(config.fleet.seed), config)

    # -- one client: I local steps of quantized SGD (eq. 4) -------------------

    def _client_update(self, params, batches, rng):
        fl = self.config.fl
        qcfg = self.config.quant
        eta = fl.learning_rate

        def step(p, inp):
            batch, key = inp
            (loss, metrics), grads = jax.value_and_grad(
                self.model.loss, has_aux=True)(p, batch, key)
            p = jax.tree_util.tree_map(
                lambda w, g: w - eta * g.astype(w.dtype), p, grads)
            return p, (loss, metrics.get("accuracy", loss * 0))

        keys = jax.random.split(rng, fl.local_iters)
        p_final, (losses, accs) = jax.lax.scan(step, params, (batches, keys))
        delta = jax.tree_util.tree_map(lambda a, b: a - b, p_final, params)
        if qcfg.enabled and qcfg.quantize_uplink:
            delta = quant.quantize_tree(delta, jax.random.fold_in(rng, 7), qcfg)
        return delta, losses.mean(), accs.mean()

    def _round(self, params, stacked_batches, client_alphas, rng):
        """stacked_batches: leaves (K, I, B, ...); returns new params + stats."""
        fl = self.config.fl
        K = fl.devices_per_round
        rngs = jax.random.split(rng, K + 1)
        with obs_trace.phase_span("sim/client_update"):
            deltas, losses, accs = jax.vmap(
                lambda b, r: self._client_update(params, b, r)
            )(stacked_batches, rngs[:K])

        lam = ch.sample_packet_success(rngs[K], (K,),
                                       self.config.channel.error_prob)
        with obs_trace.phase_span("sim/aggregate"):
            if fl.error_aware:
                new_params = agg.error_aware_aggregate(params, deltas,
                                                       client_alphas, lam)
            else:
                new_params = agg.naive_aggregate(params, deltas, lam)
        return new_params, losses.mean(), accs.mean(), lam.sum()

    def _fleet_round(self, params, fleet, k_round, stacked_batches,
                     client_alphas):
        """One fleet round, fully inside the scan: advance the channel and
        availability of the WHOLE fleet, select the cohort (masked top_k),
        run the K client updates, realize FBL-tied drops, aggregate, and
        debit the selected batteries.  All randomness derives from
        ``k_round`` (split from the single key threaded in the scan carry
        — reproducible under ``fl.seed``/``--seed``)."""
        cfg = self.config
        K = cfg.fl.devices_per_round
        k_fleet, k_cli = jax.random.split(k_round)
        fleet, info = pop_fleet.round_update(fleet, k_fleet, cfg,
                                             self.num_params, K)

        with obs_trace.phase_span("sim/client_update"):
            deltas, losses, accs = jax.vmap(
                lambda b, r: self._client_update(params, b, r)
            )(stacked_batches, jax.random.split(k_cli, K))

        with obs_trace.phase_span("sim/aggregate"):
            if cfg.fleet.error_reweight:
                new_params = pop_errors.reweighted_aggregate(
                    params, deltas, client_alphas, info.valid, info.lam,
                    cfg.channel.error_prob, rates=info.rates_sel,
                    min_rate=pop_power.min_rate(cfg, self.num_params))
            elif cfg.fl.error_aware:
                new_params = agg.error_aware_aggregate(
                    params, deltas, client_alphas * info.valid, info.lam)
            else:
                new_params = agg.naive_aggregate(params, deltas, info.lam)

        with obs_trace.phase_span("sim/telemetry"):
            tau = jnp.max(info.valid * pop_fleet.round_latency_s(
                cfg, info.rates_sel, self.num_params, self.macs))
            tel = pop_tel.simulator_round_telemetry(
                loss=losses.mean(), accuracy=accs.mean(), selected=info.idx,
                valid=info.valid, lam=info.lam, battery_j=fleet.battery_j,
                charge_j=info.charge_j, tau_s=tau, power_w=fleet.p_last,
                outage_sel=info.outage_sel, cost_sel=info.cost_sel,
                harvest_j=info.harvest_j,
                error_prob=cfg.channel.error_prob)
        return new_params, fleet, tel

    def _tap_dispatch(self, tel):
        """Host side of the in-scan io_callback: forward to the tap the
        current run_rounds call installed (no-op between runs)."""
        tap = self._active_tap
        if tap is not None:
            tap(tel)

    def _fleet_scan_fn(self, eval_fn: Optional[Callable],
                       tapped: bool) -> Callable:
        """Jitted fleet-mode lax.scan: (params, FleetState, key) carry.

        ``tapped`` bakes the streaming io_callback into the scan body (one
        compile per (eval_fn, tapped) pair); untapped bodies trace nothing
        obs-related, so their HLO is byte-identical to a no-obs build.
        """
        key = (eval_fn, tapped)
        if key not in self._fleet_scan_fns:

            def body(carry, xs):
                params, fleet, rng = carry
                batches, alphas = xs
                rng, k_round = jax.random.split(rng)
                params, fleet, tel = self._fleet_round(params, fleet,
                                                       k_round, batches,
                                                       alphas)
                if eval_fn is not None:
                    tel["accuracy"] = eval_fn(params)
                if tapped:
                    obs_tap.emit_in_scan(tel, self._tap_dispatch)
                return (params, fleet, rng), tel

            self._fleet_scan_fns[key] = jax.jit(
                lambda c, xs: jax.lax.scan(body, c, xs))
        return self._fleet_scan_fns[key]

    def _run_rounds_fleet(self, params, rounds: int, rng, *,
                          eval_fn: Optional[Callable], start_round: int,
                          return_rng: bool, tap: Optional[Callable] = None):
        """Fleet-mode multi-round driver: ONE jitted ``lax.scan`` whose
        carry threads (params, FleetState, per-round key).  The data side
        (client choice and minibatches) is prepared before the scan by
        :meth:`_chunk_inputs`, as in the legacy path: one compiled call
        for a rectangular store, per-draw for a ragged one.  Every
        per-round fleet update — fading, availability, selection, drops,
        battery debit — runs inside the scan with no host round-trips
        (the 10^6-device workload).  ``tap`` (a host callable taking the
        round telemetry dict) streams every round out of the scan while
        it runs (``repro.obs.tap``).  The host stages carry the
        ``obs.trace.SIM_HOST_SPANS`` spans."""
        (batches, alphas, _), rng_out = self._chunk_inputs(rng, rounds)
        xs = (batches, alphas)
        carry = (params, self.fleet_state,
                 jax.random.fold_in(rng, _FLEET_STREAM))
        scan_fn = self._fleet_scan_fn(eval_fn, tap is not None)
        self._active_tap = tap
        try:
            with obs_trace.host_span("sim/scan"):
                (params, fleet, _), tels = scan_fn(carry, xs)
            self.fleet_state = fleet
            # materializes (blocks), so every in-scan callback has fired
            with obs_trace.host_span("sim/history"):
                history = pop_tel.expand_history(tels, rounds, start_round)
        finally:
            self._active_tap = None
        if return_rng:
            return params, history, rng_out
        return params, history

    def _draw_chunk(self, data, table, alphas, rng, *, rounds: int):
        """Compiled body of :meth:`_chunk_inputs` on a rectangular store:
        the draws of ``rounds`` :meth:`_round_inputs` calls, key for key,
        vmapped over rounds, client slots and local steps.  ``data`` (the
        store), ``table`` (the (num_clients, L) partitions) and ``alphas``
        are arguments, so the store is never folded into the program."""
        fl = self.config.fl
        batch_size = self.config.train.global_batch
        replace = table.shape[1] < batch_size

        def next_key(rng, _):
            rng, k = jax.random.split(rng)
            return rng, k

        rng, round_keys = jax.lax.scan(next_key, rng, None, length=rounds)

        def client_batches(k_data, i, client):
            part = table[client]
            idx = jax.vmap(lambda k: jax.random.choice(
                k, part, (batch_size,), replace=replace))(
                    jax.random.split(jax.random.fold_in(k_data, i),
                                     fl.local_iters))
            return {name: v[idx] for name, v in data.items()}

        def round_inputs(k):
            k_sel, k_data, k_run = jax.random.split(k, 3)
            clients = jax.random.choice(k_sel, table.shape[0],
                                        (fl.devices_per_round,),
                                        replace=False)
            stacked = jax.vmap(functools.partial(client_batches, k_data))(
                jnp.arange(fl.devices_per_round), clients)
            return stacked, alphas[clients], k_run

        return jax.vmap(round_inputs)(round_keys), rng

    def _chunk_inputs(self, rng, rounds: int):
        """The scan inputs of ``rounds`` rounds and the advanced ``rng``:
        ``rounds`` :meth:`_round_inputs` over the chain (rng, k =
        split(rng)), stacked on a leading rounds axis, bit for bit.

        A rectangular store (every partition of one length, as
        ``partition_iid`` gives when the samples divide evenly) draws the
        whole chunk in ONE compiled call (``sim/chunk_inputs``), with no
        read back to the host.  A ragged store takes the per-draw path:
        ``rounds`` eager :meth:`_round_inputs` calls, then ``sim/stack``.
        """
        if self._part_table is not None:
            with obs_trace.host_span("sim/chunk_inputs"):
                return self._chunk_inputs_fn(self.store.data,
                                             self._part_table, self.alphas,
                                             rng, rounds=rounds)
        per_round = []
        for _ in range(rounds):
            rng, k = jax.random.split(rng)
            per_round.append(self._round_inputs(k))
        with obs_trace.host_span("sim/stack"):
            xs = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                        *per_round)
        return xs, rng

    # -- public API -------------------------------------------------------------

    def _round_inputs(self, rng):
        """Host-side per-round prep: client sampling + minibatch stacking.

        Returns (stacked_batches with (K, I, B, ...) leaves, client_alphas,
        k_run) — the exact inputs of the jitted ``_round``.  The one
        blocking read is the client choice (``sim/sync``).  This is the
        per-draw path: ``run_round`` (legacy) and a ragged store's
        :meth:`_chunk_inputs` take it; a rectangular store's chunks are
        drawn in one compiled call that gives the same bits.
        """
        with obs_trace.host_span("sim/round_inputs"):
            fl = self.config.fl
            k_sel, k_data, k_run = jax.random.split(rng, 3)
            choice = jax.random.choice(
                k_sel, self.store.num_clients, (fl.devices_per_round,),
                replace=False)
            with obs_trace.host_span("sim/sync"):
                clients = np.asarray(choice)
            batch_size = self.config.train.global_batch
            batches = []
            for i, c in enumerate(clients):
                ks = jax.random.split(jax.random.fold_in(k_data, i),
                                      fl.local_iters)
                bs = []
                for k in ks:
                    with obs_trace.host_span("sim/client_batch"):
                        bs.append(self.store.client_batch(k, int(c),
                                                          batch_size))
                batches.append(bs)
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[jax.tree_util.tree_map(lambda *l: jnp.stack(l), *bs)
                  for bs in batches])
            client_alphas = self.alphas[jnp.asarray(clients)]
            return stacked, client_alphas, k_run

    def run_round(self, params, rng) -> Tuple[PyTree, RoundTelemetry]:
        if self.fleet_state is not None:
            # fleet mode: ONE model of a round — delegate to the scan
            # driver so selection/batteries/fading advance identically
            params, hist = self._run_rounds_fleet(
                params, 1, rng, eval_fn=None, start_round=0,
                return_rng=False)
            h = hist[0]
            return params, RoundTelemetry(h["loss"], h["accuracy"],
                                          h["survivors"], h["energy_j"],
                                          h["tau_s"])
        stacked, client_alphas, k_run = self._round_inputs(rng)
        new_params, loss, acc, surv = self._round_fn(params, stacked,
                                                     client_alphas, k_run)
        e, tau = self.round_energy()
        return new_params, RoundTelemetry(float(loss), float(acc),
                                          int(surv), e, tau)

    def _scan_fn(self, eval_fn: Optional[Callable],
                 tapped: bool) -> Callable:
        """Jitted lax.scan over rounds; one compile per (eval_fn, tapped)
        pair — untapped bodies trace nothing obs-related."""
        key = (eval_fn, tapped)
        if key not in self._scan_fns:

            def body(params, xs):
                batches, alphas, k = xs
                new_params, loss, acc, surv = self._round(params, batches,
                                                          alphas, k)
                metric = eval_fn(new_params) if eval_fn is not None else acc
                if tapped:
                    obs_tap.emit_in_scan(
                        {"loss": loss, "accuracy": metric,
                         "survivors": surv}, self._tap_dispatch)
                return new_params, (loss, metric, surv)

            self._scan_fns[key] = jax.jit(
                lambda p, xs: jax.lax.scan(body, p, xs))
        return self._scan_fns[key]

    def run_rounds(self, params, rounds: int, rng, *,
                   eval_fn: Optional[Callable] = None, start_round: int = 0,
                   return_rng: bool = False,
                   tap: Optional[Callable] = None):
        """Jitted multi-round driver: one ``lax.scan`` over ``rounds``.

        Exactly reproduces ``rounds`` successive :meth:`run_round` calls —
        the same per-round PRNG chain (rng, k = split(rng)), client
        sampling and minibatch streams — but runs the whole sweep as one
        compiled scan, so multi-round benchmarks pay one dispatch instead
        of ``rounds``.  Telemetry comes back stacked and is expanded into
        the same per-round history dicts ``train`` produces; ``eval_fn``
        (a jit-able params -> scalar metric) is folded into the scan body.
        The chunk's inputs come from :meth:`_chunk_inputs`: one compiled
        call where every partition of the store has one length, the
        per-draw :meth:`_round_inputs` loop where the store is ragged.

        Fleet mode (``config.fleet.enabled``) dispatches to the fleet
        scan instead: (params, FleetState, per-round key) in the carry,
        history extended with the population telemetry, and the fleet
        persisting on ``self.fleet_state`` across calls (``run_round``
        delegates here, so both entry points advance the same fleet —
        though each call re-derives its carry key from its own ``rng``,
        so N single-round calls and one N-round scan follow different
        PRNG chains).

        ``tap`` (a host callable taking the round telemetry dict —
        usually ``obs.scan_sink_tap(sink)``) streams every round out of
        the scan via an ordered ``io_callback`` WHILE it executes;
        ``tap=None`` (default) traces nothing, keeping the lowered HLO
        byte-identical to a build without observability.
        """
        if rounds <= 0:
            return (params, [], rng) if return_rng else (params, [])
        if self.fleet_state is not None:
            return self._run_rounds_fleet(params, rounds, rng,
                                          eval_fn=eval_fn,
                                          start_round=start_round,
                                          return_rng=return_rng, tap=tap)
        xs, rng = self._chunk_inputs(rng, rounds)
        scan_fn = self._scan_fn(eval_fn, tap is not None)
        self._active_tap = tap
        try:
            with obs_trace.host_span("sim/scan"):
                params, stats = scan_fn(params, xs)
            with obs_trace.host_span("sim/history"):
                with obs_trace.host_span("sim/sync"):
                    losses, metrics, survs = jax.device_get(stats)
                e, tau = self.round_energy()
                history = [{"round": start_round + t,
                            "loss": float(losses[t]),
                            "accuracy": float(metrics[t]),
                            "survivors": int(survs[t]), "energy_j": e,
                            "tau_s": tau}
                           for t in range(rounds)]
        finally:
            self._active_tap = None
        if return_rng:
            return params, history, rng
        return params, history

    def round_energy(self) -> Tuple[float, float]:
        """Expected per-round energy (J) and latency (s) at the operating point."""
        cfg = self.config
        bits = cfg.quant.bits if cfg.quant.enabled else 32
        key = jax.random.PRNGKey(cfg.fl.seed)  # seed-reproducible MC draw
        g2 = ch.sample_rayleigh_gain2(key, (cfg.fl.num_devices,),
                                      cfg.channel.rayleigh_scale)
        rate = ch.fbl_rate(ch.snr(cfg.channel.tx_power_w, g2, cfg.channel.noise_w),
                           cfg.channel.blocklength, cfg.channel.error_prob)
        rate = jnp.maximum(rate, 1e-9)
        e = energy_mod.expected_total_energy_j(
            cfg.energy, cfg.channel, num_params=self.num_params, bits=bits,
            local_iters=cfg.fl.local_iters, rates_per_device=rate,
            num_devices=cfg.fl.num_devices,
            devices_per_round=cfg.fl.devices_per_round, rounds=1.0)
        tau = energy_mod.round_time_s(
            cfg.energy, cfg.channel, num_params=self.num_params, bits=bits,
            local_iters=cfg.fl.local_iters, macs_per_iter=self.macs,
            rates_per_device=rate, num_devices=cfg.fl.num_devices,
            devices_per_round=cfg.fl.devices_per_round)
        with obs_trace.host_span("sim/sync"):
            e, tau = jax.device_get((e, tau))
        return float(e), float(tau)

    def train(self, params, rounds: int, rng, *, target_accuracy: float = 0.0,
              eval_fn: Optional[Callable] = None, log_every: int = 0,
              chunk_rounds: int = 0,
              sink: Optional["obs_sinks.MetricsSink"] = None):
        """Run rounds until ``rounds`` or target accuracy; returns history.

        The hot path is the jitted :meth:`run_rounds` scan.  Without an
        early-stop target the whole sweep is one scan; with one, rounds run
        in ``chunk_rounds`` chunks (default 1, preserving the exact
        round-granular stop of the per-round loop) and stop as soon as the
        target metric is reached.

        ``log_every`` prints through :class:`repro.obs.sinks.ConsoleSink`
        (the one formatter interactive and streamed output share);
        ``sink`` additionally streams every round's telemetry record out
        of the jitted scan while it runs (``repro.obs``).
        """
        history = []
        console = obs_sinks.ConsoleSink(log_every=log_every) \
            if log_every else None
        chunk = chunk_rounds or (1 if target_accuracy else rounds)
        t = 0
        while t < rounds:
            n = min(chunk, rounds - t)
            tap = (obs_tap.scan_sink_tap(sink, start_round=t)
                   if sink is not None else None)
            params, hist, rng = self.run_rounds(params, n, rng,
                                                eval_fn=eval_fn,
                                                start_round=t,
                                                return_rng=True, tap=tap)
            history.extend(hist)
            if console is not None:
                for h in hist:
                    console.emit(obs_sinks.make_record("fl_round",
                                                       h["round"], h))
            t += n
            if target_accuracy and any(h["accuracy"] >= target_accuracy
                                       for h in hist):
                break
        return params, history


# ---------------------------------------------------------------------------
# distributed FL round (shard_map over pod/data, auto over model)
# ---------------------------------------------------------------------------

def fl_data_axes(mesh, config: Optional[Config] = None) -> Tuple[str, ...]:
    wanted = config.fl.cohort_axes if config is not None else ("pod", "data")
    return tuple(a for a in wanted if a in mesh.shape)


_WIRE_TO_COLLECTIVE = {"f32": "paper", "int": "int", "packed": "packed",
                       "ring": "ring", "rsag": "rsag", "auto": "auto"}
#: every value make_fl_round accepts ("auto" resolves to a concrete mode);
#: canonical tuple lives jax-free in config.base for the CLI launchers
COLLECTIVE_CHOICES = base_mod.COLLECTIVE_CHOICES


def resolve_collective(config: Config, collective: Optional[str]) -> str:
    """Explicit ``collective`` wins; else ``config.quant.wire_format``."""
    if collective is None:
        collective = _WIRE_TO_COLLECTIVE.get(config.quant.wire_format)
        if collective is None:
            raise ValueError(
                f"unknown quant.wire_format {config.quant.wire_format!r}; "
                f"expected one of {sorted(_WIRE_TO_COLLECTIVE)}")
    if collective not in COLLECTIVE_CHOICES:
        raise ValueError(f"unknown collective {collective!r}")
    return collective


def make_fl_round(model, config: Config, mesh, *,
                  collective: Optional[str] = None,
                  tap: Optional[Callable] = None) -> Optional[Callable]:
    """Build the jit-able distributed FL round.

    collective: "paper" (f32 wire, faithful) | "int" (integer-code wire)
    | "packed" (bit-packed uint32 wire, matching the paper's payload_bits
    accounting) | "ring" (native-width ppermute ring, no guard bits)
    | "rsag" (reduce-scatter + all-gather, growing lane widths)
    | "auto" (cost-model pick of the byte-minimal mode for this mesh)
    | None (the default — resolve ``config.quant.wire_format``).

    Returned fn: (params, batch, rng) -> (params, metrics) — or, when
    ``config.fleet.enabled``, (params, batch, rng, fleet) ->
    (params, metrics, fleet) with a ``population.fleet.FleetState``
    threaded through (replicated): the fleet advances its AR(1) fading /
    availability, a jit-able policy selects one device per cohort shard,
    λ realizes from each device's FBL operating point, and the selected
    batteries are debited — identical under every collective mode.

    ``batch`` leaves are (global_batch, ...) sharded over the data axes;
    each shard is one client cohort.  ``metrics["wire_bits_per_param"]``
    reports the bits each device actually puts on the wire per parameter
    (after "auto" resolution and degenerate fallbacks — e.g. "packed"
    silently becomes "int" when the guard lane exceeds 32 bits), the
    number energy accounting must charge; the per-phase split rides next
    to it as ``metrics["wire_phase_bits_per_param"]`` (e.g. rsag's
    reduce_scatter/all_gather legs — ``population.telemetry``).

    ``tap`` (a host callable taking (metrics dict, round index) —
    usually ``obs.step_sink_tap(sink)``) streams each round's metrics out
    of the jitted step via ``io_callback`` while it executes; the callback
    sits after the shard_map, on the replicated metrics, so it fires once
    per round.  A TAPPED round fn takes one extra trailing argument — a
    replicated int32 ``step`` scalar — whose value stamps the streamed
    record: the callback is unordered, so with async dispatch the host
    cannot number records by arrival.  ``tap=None`` traces nothing — the
    lowered HLO is byte-identical to a no-obs build, and the signature
    stays exactly as documented above.
    """
    fl = config.fl
    qcfg = config.quant
    collective = resolve_collective(config, collective)
    axes = fl_data_axes(mesh, config)
    if not axes:
        # no cohort axis on this mesh (e.g. FSDP arch on a single pod):
        # the FL round degenerates to standard training — caller falls back.
        return None
    axis_sizes = tuple(int(mesh.shape[a]) for a in axes)
    num_shards = int(np.prod(axis_sizes))
    plan = agg.make_wire_plan(collective, qcfg, axes, axis_sizes)
    eta = fl.learning_rate
    with_fleet = config.fleet.enabled
    if with_fleet:
        if config.fleet.size < num_shards:
            raise ValueError(
                f"fleet.size={config.fleet.size} smaller than the cohort "
                f"shard count {num_shards}")
        num_params = int(sum(
            np.prod(s.shape) for s in jax.tree_util.tree_leaves(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)))))

    def _cohort_update(params, batch, rng, lam, delta_scale=None):
        """I local steps + planned collective for ONE cohort shard; ``rng``
        is already the per-shard stream, ``lam`` this cohort's λ.
        ``delta_scale`` rescales the aggregated delta after the collective
        (the fleet's opt-in IPW correction — a replicated scalar, so every
        wire format stays bit-identical)."""
        # split the cohort batch into I microbatches (the ξ_k stream, eq. 4);
        # the remainder (local_batch % I) is dropped
        I = fl.local_iters
        micro = jax.tree_util.tree_map(
            lambda x: x[: (x.shape[0] // I) * I].reshape(
                (I, x.shape[0] // I) + x.shape[1:]), batch)

        def step(p, inp):
            mb, key = inp
            (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
                p, mb, key)
            p = jax.tree_util.tree_map(
                lambda w, g: w - eta * g.astype(w.dtype), p, grads)
            return p, loss

        keys = jax.random.split(rng, I)
        with obs_trace.phase_span("fl/local_steps"):
            p_local, losses = jax.lax.scan(step, params, (micro, keys))
        delta = jax.tree_util.tree_map(lambda a_, b_: (a_ - b_).astype(jnp.float32),
                                       p_local, params)

        alpha = jnp.float32(1.0 / num_shards)
        k_q = jax.random.fold_in(rng, 13)
        agg_delta = agg.aggregate(plan, delta, alpha, lam, k_q)
        if delta_scale is not None:
            agg_delta = jax.tree_util.tree_map(lambda d: d * delta_scale,
                                               agg_delta)

        with obs_trace.phase_span("fl/apply"):
            new_params = jax.tree_util.tree_map(
                lambda w, d: w + d.astype(w.dtype), params, agg_delta)
        mean_loss = jax.lax.pmean(losses.mean(), axes)
        survivors = jax.lax.psum(lam, axes)
        return new_params, mean_loss, survivors

    def _shard_rng(rng):
        # distinct PRNG stream per client cohort (shard of the data axes)
        for a in axes:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(a))
        return rng

    def _cohort_index():
        # flat cohort index over the DATA axes only — the identity every
        # model-axis replica of one cohort shares; cohort-shaped vectors
        # (FleetRoundInfo.lam, length num_shards) are indexed with it
        shard = jnp.int32(0)
        for a, s in zip(axes, axis_sizes):
            shard = shard * s + jax.lax.axis_index(a)
        return shard

    def local_round(params, batch, rng):
        rng = _shard_rng(rng)
        lam = ch.sample_packet_success(jax.random.fold_in(rng, 11), (),
                                       config.channel.error_prob)
        new_params, mean_loss, survivors = _cohort_update(params, batch,
                                                          rng, lam)
        metrics = pop_tel.distributed_metrics(
            plan, loss=mean_loss, survivors=survivors)
        return new_params, metrics

    def fleet_round(params, batch, rng, fleet):
        # the fleet update is REPLICATED: identical inputs (fleet, raw rng)
        # on every shard compute the identical selection, so each shard
        # just reads its own λ at its flat cohort index — no collective.
        # battery pricing deliberately uses the wire-format-INDEPENDENT
        # d·n payload (round_cost_j default), not plan.wire_bits: the
        # fleet trajectory (batteries -> eligibility -> selection -> λ)
        # must be identical under every collective so the aggregated
        # model stays bit-identical across wire formats (the acceptance
        # invariant test_distributed asserts).  The realised per-phase
        # wire bits ride in the metrics for infrastructure accounting;
        # callers wanting wire-priced debits pass wire_bits_per_param.
        fleet, info = pop_fleet.round_update(
            fleet, jax.random.fold_in(rng, _FLEET_STREAM), config,
            num_params, num_shards)
        delta_scale = None
        if config.fleet.error_reweight:
            delta_scale = pop_errors.ipw_delta_scale(
                info.lam, info.valid, info.rates_sel,
                config.channel.error_prob,
                min_rate=pop_power.min_rate(config, num_params))

        new_params, mean_loss, survivors = _cohort_update(
            params, batch, _shard_rng(rng), info.lam[_cohort_index()],
            delta_scale)

        metrics = pop_tel.distributed_metrics(
            plan, loss=mean_loss, survivors=survivors,
            fleet=pop_tel.fleet_round_metrics(
                battery_j=fleet.battery_j, valid=info.valid,
                charge_j=info.charge_j, power_w=fleet.p_last,
                outage_sel=info.outage_sel, cost_sel=info.cost_sel,
                harvest_j=info.harvest_j,
                error_prob=config.channel.error_prob))
        return new_params, metrics, fleet

    P = jax.sharding.PartitionSpec
    batch_spec = P(axes if len(axes) > 1 else axes[0])
    batch_specs = jax.tree_util.tree_map(lambda _: batch_spec,
                                         _batch_structure(model, config))
    metric_specs = jax.tree_util.tree_map(
        lambda _: P(), pop_tel.distributed_metrics_structure(plan,
                                                             with_fleet))
    if with_fleet:
        round_fn = compat.shard_map(
            fleet_round, mesh=mesh,
            in_specs=(P(), batch_specs, P(), P()),
            out_specs=(P(), metric_specs, P()),
            check_vma=False, axis_names=set(axes))
    else:
        round_fn = compat.shard_map(
            local_round, mesh=mesh,
            in_specs=(P(), batch_specs, P()),
            out_specs=(P(), metric_specs),
            check_vma=False, axis_names=set(axes))
    if tap is None:
        return round_fn

    def tapped_round(*args):
        # the trailing replicated int32 ``step`` stamps the streamed record
        *args, step = args
        out = round_fn(*args)
        obs_tap.emit_step(out[1], step, tap)
        return out

    return tapped_round


def _batch_structure(model, config: Config):
    """A pytree with the same structure as a training batch (specs only)."""
    if config.model.family == "cnn":
        return {"images": 0, "labels": 0}
    if config.model.is_encoder_decoder:
        return {"tokens": 0, "labels": 0, "frames": 0}
    return {"tokens": 0, "labels": 0}
