"""AlertMixPipeline — end-to-end assembly of the paper's architecture
(Fig. 2 + the SQS pull logic of Fig. 3):

  Scheduler/Cron -> StreamsPicker (ShardedStreamRegistry)
    -> ChannelDistributor (channels REGISTERED at runtime)
    -> per-channel {main, priority} queues
    -> FeedRouter (replenish-to-optimal worker mailbox)
    -> BalancingPool workers (+ OptimalSizeExploringResizer)
         worker: Connector.fetch (repro.ingest — conditional GET /
                 file tail / log re-ingest / push drain, per the
                 source's registered connector) -> redirect handling
                 -> dedup -> enrich
                 -> delivery layer (BatchingSink -> FanOutSink -> one
                    RetryingSink per backend, each optionally on its
                    own dispatcher thread behind a bounded hand-off
                    queue — ``delivery_dispatch``; repro.delivery);
                 StreamsUpdater marks processed (cursor advances,
                 connector backoff hints fold into next_due)
    -> DeadLettersListener monitors every bounded mailbox AND delivery
       failures (reason="delivery_failed:<backend>")

Ingestion is pluggable (repro.ingest): sources name a Connector, the
registry is hash-sharded (``PipelineConfig.registry_shards``), and the
runtime control API — ``add_source`` / ``remove_source`` / ``pause`` /
``resume`` / ``register_channel`` / ``register_connector`` /
``list_sources`` / ``push`` — adds, parks, and removes sources and whole
channels while the system runs (the paper's incremental-flexibility
claim, now a first-class surface).

Flow control, both directions:

  egress   ``PipelineConfig.delivery_dispatch`` moves every backend onto
           its own dispatcher thread behind a bounded hand-off queue
           (repro.delivery.dispatch): a stalled backend inflates only
           its own queue depth and lag — never its siblings' emit
           latency, never the worker loop; overflow dead-letters under
           ``dispatch_overflow:<backend>``.
  ingress  connectors return ``FetchResult.backoff_hint_s`` (HTTP 429 /
           Retry-After analogue); the registry folds it into next_due
           so polled sources slow a hot upstream instead of hammering
           it.  Per-connector fetch/backoff counters surface in
           ``connector_stats()`` / ``Metrics.ingest``.

Durability plane (``PipelineConfig.store_dir``; repro.store): accepted
documents are teed into an append-only checksummed EventLog, every dead
letter is journaled with its reason, and when a failed backend's health
flips back up the ReplayEngine re-delivers its ``delivery_failed:*``
backlog through the backend's own retry envelope (dedup-idempotent).

Runs against a VIRTUAL clock (``run_for``) so the paper's 24h/200k-source
experiment replays in seconds, or incrementally via ``step``.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from repro.core.dead_letters import DeadLettersListener
from repro.core.dedup import DedupWindow, content_hash
from repro.core.pool import BalancingPool
from repro.core.queues import BoundedPriorityQueue, Message
from repro.core.resizer import OptimalSizeExploringResizer
from repro.core.router import FeedRouter
from repro.core.scheduler import DEFAULT_CHANNELS, ChannelDistributor, Scheduler
from repro.core.sinks import IndexSink
from repro.core.sources import NOT_MODIFIED, SourceSimulator
from repro.delivery import BatchingSink, FanOutSink, RetryingSink, as_sink
from repro.obs import (LatencySink, Observability, TracingSink,
                       columnar_blocks_decoded, kernel_launches,
                       pack_sessions, pack_slot_index, unpack_order)

# repro.ingest imports repro.core.registry (which runs this package's
# __init__) — import it lazily to keep `import repro.ingest` first legal
def _ingest():
    import repro.ingest as ingest
    return ingest


@dataclass
class PipelineConfig:
    num_sources: int = 1000
    pick_interval_s: float = 5.0       # cron period (paper: 5 seconds)
    feed_interval_s: float = 300.0     # per-source refresh (paper: 5 min)
    queue_capacity: int = 100_000
    mailbox_capacity: int = 4096
    optimal_buffer: int = 256          # FeedRouter target
    replenish_after: int = 64
    replenish_timeout_s: float = 1.0
    workers: int = 8
    resizer: bool = True
    dedup_window: int = 1 << 16
    channel_mix: Dict[str, float] = field(default_factory=lambda: {
        "news": 0.70, "custom_rss": 0.15, "facebook": 0.08, "twitter": 0.07,
    })
    # ---- ingestion plane (repro.ingest) ------------------------------------
    registry_shards: int = 1           # hash shards (locks/heaps) in the
                                       # stream registry; 1 = the seed's
                                       # single-lock behaviour
    push_capacity: int = 10_000        # per-source PushConnector buffer bound
    # ---- analytics stage (repro.alerts) ------------------------------------
    analytics: bool = False            # mount the windowed-analytics stage
    window_kind: str = "tumbling"      # tumbling | sliding | session
    window_size_s: float = 300.0       # event-time window width
    window_gap_s: float = 30.0         # session only: idle gap that
                                       # closes a key's session
    # the lateness budget must cover the fetch cadence: a document can be
    # published right after one conditional GET and only be seen ~one
    # feed_interval_s later, which is event-time lateness by construction
    allowed_lateness_s: float = 300.0  # late events within this still count
    watermark_lag_s: float = 60.0      # bounded out-of-orderness
    alerts_history: int = 10_000       # AlertSink retention: fired_alerts()
                                       # keeps the newest N (by_rule totals
                                       # stay complete), so long soaks hold
                                       # steady memory — the alert-side
                                       # mirror of metrics_history
    # ---- query/serving plane (repro.query) ---------------------------------
    query: bool = False                # mount the materialized-aggregate
                                       # query plane (implies analytics)
    query_staleness_s: Optional[float] = 900.0  # refuse queries when the
                                       # serving watermark lags now by
                                       # more than this (None = never)
    query_cache_entries: int = 1024    # watermark-invalidated result cache
    query_max_windows_per_key: int = 4096  # hot retention per key; older
                                       # windows answer via EventLog replay
    # ---- delivery layer (repro.delivery) -----------------------------------
    delivery_batch: int = 16           # records per backend write (1 = sync)
    delivery_max_delay_s: float = 5.0  # virtual-time bound on buffering
    delivery_retry_attempts: int = 3   # per-backend attempts before DLQ
    delivery_retry_backoff_s: float = 2.0  # first backoff (then x2 each)
    # flow control (repro.delivery.dispatch): True moves every backend
    # onto its own dispatcher thread behind a bounded hand-off queue —
    # one stalled backend inflates only its own queue depth/lag, never
    # its siblings' emit latency or the worker loop.  False keeps the
    # seed's serial in-worker delivery, which is fully deterministic
    # under the virtual clock (retries/health flips land at exact
    # virtual times) — the right mode for replaying experiments.
    delivery_dispatch: bool = False
    dispatch_capacity: int = 256       # hand-off queue bound (batches)
    dispatch_flush_deadline_s: float = 10.0  # wall-clock drain bound on
                                       # flush/close (stalled backends
                                       # cannot wedge the producer)
    # ---- durability plane (repro.store) ------------------------------------
    store_dir: Optional[str] = None    # mount the durable log/journal plane
    segment_bytes: int = 1 << 20       # event-log segment roll size
    segment_age_s: Optional[float] = None  # optional age roll (virtual time)
    store_fsync: bool = False          # fsync every append (durable, slower)
    replay_auto: bool = True           # auto-replay delivery_failed:* when a
                                       # backend's health flips back up
    replay_batch: int = 256            # records per replay emit
    replay_dedup_window: int = 1 << 16  # replay idempotency window
    replay_late_on_flush: bool = True  # drain the late_event journal
                                       # through the batch path at every
                                       # flush_delivery (also unpins the
                                       # journal's truncation floor, so
                                       # disk is reclaimed; off = late
                                       # backlog kept for manual replay)
    # ---- columnar store plane (repro.store.columnar) -----------------------
    store_columnar: bool = False       # seal segments as binary columnar
                                       # blocks; replay + cold queries read
                                       # column lanes (zero per-record
                                       # Python on sealed data)
    columnar_block_rows: int = 2048    # rows per columnar block (the
                                       # pruning + checksum granularity)
    compact_interval_s: Optional[float] = None  # keyed compaction cadence
                                       # (keep-last-per-doc-id); None = off
    compact_head_segments: int = 2     # newest sealed segments compaction
                                       # never touches (the dirty head)
    retention_max_bytes: Optional[int] = None   # sealed-bytes budget;
                                       # oldest segments released beyond it
    retention_max_age_s: Optional[float] = None  # event-time age budget
    offload_dir: Optional[str] = None  # object-store dir for tiered
                                       # offload of sealed segments;
                                       # None = keep everything local
    offload_keep_local: int = 2        # newest sealed segments kept local
    # ---- observability plane (repro.obs) ------------------------------------
    trace_sample_rate: float = 0.0     # fraction of roots traced; 0 = off
                                       # (span() short-circuits, records
                                       # carry no trace id — the seed's
                                       # exact behaviour)
    trace_capacity: int = 4096         # flight-recorder span ring bound
    trace_export_dir: Optional[str] = None  # JSONL span export (None = off)
    metrics_history: int = 8192        # ring bound on the Metrics
                                       # sent/received/deleted series
                                       # (0/None = unbounded, the seed's
                                       # leak)
    # self-monitoring loop: sample the metrics registry every this many
    # virtual seconds into the __health__ channel so the rule engine
    # alarms on the platform itself (None = off)
    selfmon_interval_s: Optional[float] = None
    selfmon_rules: Optional[list] = None   # override the default health
                                       # rules (dead-letter flood +
                                       # backend-lag anomaly)
    selfmon_dead_letter_threshold: float = 100.0  # flood rule bound
                                       # (dead letters per window)
    # ---- latency & SLO plane (repro.obs.latency / repro.obs.slo) -----------
    latency_tracking: bool = True      # always-on per-plane + end-to-end
                                       # latency histograms, independent of
                                       # trace_sample_rate (False exists for
                                       # overhead baselines, not production)
    slos: Optional[list] = None        # SLOSpec list; None/[] = no SLO
                                       # engine mounted.  Burn gauges feed
                                       # the selfmon loop when it is on, so
                                       # violations fire as ordinary
                                       # __health__ alerts
    slo_sample_interval_s: float = 30.0  # virtual-clock cadence for sampled
                                       # indicators (watermark lag, query
                                       # staleness, delivery ratio) + burn
                                       # gauge refresh + dispatcher
                                       # queue-depth sampling


@dataclass
class Metrics:
    """Per-interval counters — the CloudWatch charts of Fig. 4.

    The time series (``sent``/``received``/``deleted``) are bounded
    rings: ``history`` keeps the newest N points (the chart window) so a
    long-lived pipeline holds steady memory.  ``history=0``/``None``
    keeps them unbounded lists."""

    sent: List[tuple] = field(default_factory=list)      # (t, n) enqueued
    received: List[tuple] = field(default_factory=list)  # (t, n) processed
    deleted: List[tuple] = field(default_factory=list)   # (t, n) completed
    history: Optional[int] = None
    indexed_total: int = 0
    fetched_total: int = 0
    not_modified_total: int = 0
    redirects_total: int = 0
    duplicates_total: int = 0
    malformed_total: int = 0
    fetch_errors_total: int = 0        # connector raised; source backed off
    alerts_total: int = 0
    windows_closed_total: int = 0
    replayed_total: int = 0            # records re-delivered from the journal
    # delivery-layer counters, refreshed at flush_delivery (run_for does
    # this at its cutoff): top-level emitted/pending plus
    # {backend: emitted/retried/dead_lettered/lag/healthy}; with
    # delivery_dispatch, each backend also reports queue_depth /
    # handoff_p99_ms / dropped (the flow-control symptoms)
    delivery: dict = field(default_factory=dict)
    # durability-plane counters (repro.store), refreshed with delivery:
    # appended/replayed/pending records + bytes + segments
    store: dict = field(default_factory=dict)
    # per-connector ingress counters, refreshed with delivery:
    # {connector: fetches/items/not_modified/errors/backoffs/deferred_s}
    ingest: dict = field(default_factory=dict)
    # query-plane counters (repro.query), refreshed with delivery:
    # queries/cache hits+misses/stale rejections/cold scans + store
    # segment/watermark state (empty dict when the plane is off)
    query: dict = field(default_factory=dict)
    # SLO-plane report (repro.obs.slo), refreshed with delivery: per-SLO
    # good/bad counts, budget remaining, fast/slow burn rates, and the
    # currently-burning sets (empty dict when no SLOs are configured)
    slo: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.history:
            self.sent = collections.deque(self.sent, maxlen=self.history)
            self.received = collections.deque(self.received,
                                              maxlen=self.history)
            self.deleted = collections.deque(self.deleted,
                                             maxlen=self.history)


class AlertMixPipeline:
    def __init__(self, cfg: PipelineConfig, *, seed: int = 0,
                 sinks: Optional[list] = None,
                 item_hook: Optional[Callable] = None,
                 analytics_rules: Optional[list] = None):
        self.cfg = cfg
        self.now = 0.0
        # ---- observability plane (repro.obs): one metrics registry + one
        # tracer for every plane.  Ingress accounting is NATIVE registry
        # counters (the old dict-of-dicts + its second lock are gone);
        # everything whose counters live elsewhere (sinks, store,
        # scheduler, dead letters) is adopted by the _sync_registry
        # collector, so snapshot()/render_prometheus() are always whole.
        self.obs = Observability(
            sample_rate=cfg.trace_sample_rate,
            trace_capacity=cfg.trace_capacity,
            export_dir=cfg.trace_export_dir, seed=seed)
        self.tracer = self.obs.tracer
        reg = self.obs.metrics
        self._m_fetches = reg.counter(
            "ingest_fetches_total", "connector fetches attempted")
        self._m_items = reg.counter(
            "ingest_items_total", "feed items returned by fetches")
        self._m_not_modified = reg.counter(
            "ingest_not_modified_total", "conditional-GET 304 responses")
        self._m_fetch_errors = reg.counter(
            "ingest_fetch_errors_total", "connector fetches that raised")
        self._m_backoffs = reg.counter(
            "ingest_backoffs_total",
            "fetches whose backoff hint deferred the source beyond its "
            "own interval")
        self._m_deferred = reg.counter(
            "ingest_deferred_seconds_total",
            "total extra deferral seconds applied by backoff hints")
        self._m_fetch_seconds = reg.histogram(
            "ingest_fetch_seconds", "wall-clock connector fetch latency")
        reg.add_collector(self._sync_registry)
        # ---- latency & SLO plane (repro.obs.latency / repro.obs.slo):
        # always-on latency histograms — independent of trace sampling by
        # design, so SLO measurement never depends on sample_rate — feed a
        # declarative SLO engine doing multi-window burn-rate accounting
        # on the virtual clock
        self.slo = None
        if cfg.slos:
            from repro.obs.slo import SLOEngine
            self.slo = SLOEngine(cfg.slos, reg,
                                 sample_interval_s=cfg.slo_sample_interval_s)
        self.latency = None
        self._last_dispatch_sample = float("-inf")
        if cfg.latency_tracking:
            from repro.obs.latency import LatencyTracker
            self.latency = LatencyTracker(reg, clock=lambda: self.now,
                                          slo=self.slo)
            self._h_dispatch_depth = reg.histogram(
                "dispatch_queue_depth_sampled",
                "hand-off queue depth per backend, sampled at the SLO "
                "cadence")
            self._h_dispatch_handoff = reg.histogram(
                "dispatch_handoff_p99_ms_sampled",
                "hand-off p99 queue wait per backend, sampled at the SLO "
                "cadence")
        # ---- durability plane (repro.store): mounted before anything that
        # can dead-letter, so every published record is journaled from t=0
        self.store = None
        if cfg.store_dir:
            from repro.store import StorePlane
            self.store = StorePlane(
                cfg.store_dir, segment_bytes=cfg.segment_bytes,
                segment_age_s=cfg.segment_age_s, fsync=cfg.store_fsync,
                replay_dedup_window=cfg.replay_dedup_window,
                columnar=cfg.store_columnar,
                block_rows=cfg.columnar_block_rows,
                compact_interval_s=cfg.compact_interval_s,
                compact_head_segments=cfg.compact_head_segments,
                retention_max_bytes=cfg.retention_max_bytes,
                retention_max_age_s=cfg.retention_max_age_s,
                offload_dir=cfg.offload_dir,
                offload_keep_local=cfg.offload_keep_local)
        self.dead_letters = DeadLettersListener(
            journal=None if self.store is None else self.store.journal)
        if self.store is not None and self.store.columnar:
            # cold-fetch failures / compaction conflicts surface through
            # the same taxonomy (and journal) as every other drop
            self.store.log.dead_letters = self.dead_letters
        ingest = _ingest()
        self.registry = ingest.ShardedStreamRegistry(
            shards=cfg.registry_shards, lease_s=cfg.feed_interval_s * 2)
        # pluggable ingress: the simulator is just one registered
        # connector; jsonl/eventlog/custom ones arrive via
        # register_connector, push ingress via push()
        self.sim = SourceSimulator(seed=seed)
        self._cursor_cls = ingest.Cursor
        self.connectors = ingest.ConnectorRegistry()
        self.connectors.register(ingest.SimulatorConnector(self.sim))
        self.connectors.register(ingest.PushConnector(
            capacity=cfg.push_capacity, dead_letters=self.dead_letters))
        self.item_hook = item_hook
        self.metrics = Metrics(history=cfg.metrics_history)

        # ---- delivery layer: every accepted document flows through ONE
        # FanOutSink; each backend gets its own retry envelope (exponential
        # backoff -> dead letters) and the whole fan-out sits behind a
        # batching stage flushed by size or virtual time.  With
        # cfg.delivery_dispatch each retry envelope additionally rides its
        # own dispatcher thread behind a bounded hand-off queue, so a
        # stalled backend's latency is isolated too, not just its failures
        self.sinks = list(sinks) if sinks is not None else [IndexSink()]
        backends = []
        for s in self.sinks:
            terminal = as_sink(s)
            write_target = terminal
            if self.tracer.enabled:
                # inside the retry envelope so EVERY attempt — first try,
                # backoff retry, dispatcher-thread write, replay — records
                # a delivery.write span; named after the terminal so the
                # delivery_failed:<backend> reason key is unchanged
                write_target = TracingSink(terminal, self.tracer,
                                           name=terminal.name)
            if self.latency is not None:
                # also inside the retry envelope: every attempt's wall
                # cost lands in plane_latency{plane="delivery.write"},
                # and a record's end-to-end latency is measured at the
                # moment its write LANDS (batching delay, retry backoff,
                # and replay outages all count)
                write_target = LatencySink(write_target, self.latency,
                                           name=terminal.name)
            backend = RetryingSink(
                write_target,
                max_attempts=cfg.delivery_retry_attempts,
                backoff_s=cfg.delivery_retry_backoff_s,
                dead_letters=self.dead_letters,
                name=terminal.name)        # metrics key by the backend
            if cfg.delivery_dispatch:
                from repro.delivery import DispatchingSink
                backend = DispatchingSink(
                    backend, capacity=cfg.dispatch_capacity,
                    flush_deadline_s=cfg.dispatch_flush_deadline_s,
                    dead_letters=self.dead_letters,
                    name=terminal.name)    # stable key across modes
            backends.append(backend)
        self.fan_out = FanOutSink(backends, name="documents")
        if cfg.delivery_batch > 1:
            self.delivery = BatchingSink(
                self.fan_out, max_batch=cfg.delivery_batch,
                max_delay_s=cfg.delivery_max_delay_s)
        else:
            self.delivery = self.fan_out

        # channels are REGISTERED, not hardcoded: each registration
        # creates the {main, priority} queue pair (Fig. 2 routers) and a
        # FeedRouter, and re-splits the optimal buffer across routers.
        # The channel_mix keys seed the initial set; register_channel
        # opens more at runtime.
        self.distributor = ChannelDistributor(dead_letters=self.dead_letters)
        self.main_queues = self.distributor.main_queues       # live views
        self.priority_queues = self.distributor.priority_queues
        self.scheduler = Scheduler(
            self.registry, self.distributor,
            interval_s=cfg.pick_interval_s)
        self.mailbox = BoundedPriorityQueue(
            cfg.mailbox_capacity, dead_letters=self.dead_letters)
        self.routers: List[FeedRouter] = []
        # keep the seed's historical registration order for the default
        # channels: router order sets the mailbox interleaving, and the
        # training plane's checkpoint-parity depends on that trajectory
        initial = [c for c in DEFAULT_CHANNELS if c in cfg.channel_mix]
        initial += [c for c in cfg.channel_mix if c not in DEFAULT_CHANNELS]
        for c in initial:
            self.register_channel(c)
        self.dedup = DedupWindow(cfg.dedup_window)
        resizer = OptimalSizeExploringResizer(
            lower=1, upper=max(64, cfg.workers * 4), seed=seed) if cfg.resizer else None
        self.pool = BalancingPool(self.mailbox, self._work, size=cfg.workers,
                                  resizer=resizer)

        # optional windowed-analytics + alert-rule stage (repro.alerts):
        # worker-enriched documents flow in keyed by channel — or by an
        # explicit doc["key"]/doc["value"], which is how the __health__
        # stream carries metric series; the pipeline's virtual clock
        # drives the watermark; late events -> dead letters
        self.analytics = None
        if (cfg.analytics or cfg.query or analytics_rules is not None
                or cfg.selfmon_interval_s is not None):
            from repro.alerts import AnalyticsStage, ThresholdRule, WindowSpec
            from repro.store.lanes import doc_key
            if analytics_rules is not None:
                rules = list(analytics_rules)
            elif cfg.analytics:
                rules = [ThresholdRule("volume_spike", metric="count",
                                       op=">=", threshold=50.0)]
            else:
                rules = []      # self-monitoring/query only: no product rules
            self.analytics = AnalyticsStage(
                WindowSpec(kind=cfg.window_kind, size_s=cfg.window_size_s,
                           gap_s=cfg.window_gap_s,
                           allowed_lateness_s=cfg.allowed_lateness_s),
                rules,
                watermark_lag_s=cfg.watermark_lag_s,
                dead_letters=self.dead_letters,
                key_fn=doc_key,
                value_fn=lambda doc: float(doc.get("value", 1.0)),
                alerts_keep_last=cfg.alerts_history)
            self.analytics.tracer = self.tracer
        # ---- query/serving plane (repro.query): closed windows fold into
        # materialized per-(key, window) segments via the analytics export
        # hook; queries below the retention floor replay the EventLog
        # through the Pallas batch path (when a store plane is mounted)
        self.query = None
        if cfg.query:
            from repro.query import QueryPlane
            self.query = QueryPlane(
                self.analytics,
                log=None if self.store is None else self.store.log,
                staleness_s=cfg.query_staleness_s,
                cache_entries=cfg.query_cache_entries,
                max_windows_per_key=cfg.query_max_windows_per_key,
                clock=lambda: self.now,
                dead_letters=self.dead_letters,
                tracer=self.tracer if self.tracer.enabled else None)
        if self.store is not None:
            # the replay engine aggregates through the SAME rule-engine
            # state the live WindowOperator feeds (batch/live unification)
            self.store.replay.analytics = self.analytics
            self.store.replay.tracer = self.tracer
            if self.store.columnar:
                self.store.log.tracer = \
                    self.tracer if self.tracer.enabled else None
        # per-backend health, tracked across steps so a False -> True flip
        # (backend recovery) can trigger an automatic journal replay
        self._backend_health: Dict[str, bool] = {
            b.terminal.name: b.healthy for b in self.fan_out.backends}

        # sampled SLO indicators (per-channel watermark lag, query-plane
        # staleness, delivery success ratio) pull at a fixed virtual
        # cadence from step() — monitoring reads (collectors, status
        # calls) never mutate SLO state
        self._slo_delivery_prev = (0.0, 0.0)
        if self.slo is not None:
            self.slo.add_sampler(self._slo_sample)

        # ---- self-monitoring loop (repro.obs.selfmon): the registry
        # re-enters the platform as an ordinary stream on the __health__
        # channel — registered connector, scheduled source, normal worker
        # path — so the rule engine above alarms on the platform itself
        self.selfmon = None
        self.selfmon_sid = None
        if cfg.selfmon_interval_s is not None:
            from repro.alerts import ThresholdRule, ZScoreRule
            from repro.obs.selfmon import HEALTH_CHANNEL, MetricsConnector
            self.selfmon = MetricsConnector(self.obs.metrics)
            self.connectors.register(self.selfmon)
            self.selfmon_sid = self.add_source(
                HEALTH_CHANNEL, url="obs://registry",
                interval_s=cfg.selfmon_interval_s,
                first_due=cfg.selfmon_interval_s,
                connector=self.selfmon.name)
            health_rules = cfg.selfmon_rules
            if health_rules is None:
                health_rules = [
                    # dead-letter flood: the journal growing by more than
                    # the bound inside one window (counters publish
                    # per-sample deltas; windows sum them into a rate)
                    ThresholdRule(
                        "selfmon_dead_letter_flood", metric="sum", op=">=",
                        threshold=cfg.selfmon_dead_letter_threshold,
                        severity="critical",
                        key_prefix="__health__.dead_letters_total"),
                    # backend lag departing its own history (gauges
                    # publish levels; z-score learns the usual level)
                    ZScoreRule(
                        "selfmon_backend_lag_anomaly", metric="mean",
                        z=3.0, severity="warning",
                        key_prefix="__health__.delivery_lag"),
                ]
            if self.slo is not None:
                # the SLO engine publishes NORMALIZED burn gauges
                # (>= 1.0 = alert), so burn alerting is a plain
                # threshold at 1.0 over the
                # __health__.slo_fast_burn.<slo> level series — SLO
                # violations become ordinary alerts with the ordinary
                # delivery/dead-letter machinery behind them
                health_rules = list(health_rules) + [
                    ThresholdRule(
                        "selfmon_slo_fast_burn", metric="max", op=">=",
                        threshold=1.0, severity="critical",
                        key_prefix="__health__.slo_fast_burn"),
                    ThresholdRule(
                        "selfmon_slo_slow_burn", metric="max", op=">=",
                        threshold=1.0, severity="warning",
                        key_prefix="__health__.slo_slow_burn"),
                ]
            for rule in health_rules:
                self.analytics.engine.add_rule(rule)

        # populate the registry (incremental add — sources spread over the
        # first interval so picks don't all collide at t=0)
        import random
        rng = random.Random(seed)
        chans, weights = zip(*cfg.channel_mix.items())
        for i in range(cfg.num_sources):
            self.registry.add_source(
                rng.choices(chans, weights)[0],
                url=f"https://feeds.example/{i}.xml",
                interval_s=cfg.feed_interval_s,
                first_due=rng.random() * cfg.feed_interval_s,
                seed=i,
            )

    # ---- Worker (paper): connector fetch, redirects, dedup, process -------
    def _work(self, msg: Message) -> None:
        src = self.registry.get(msg.sid)
        if src is None:
            return
        if src.paused:
            # paused after pick: hand the lease back untouched so the
            # source is pickable the moment it's resumed, not a full
            # lease later
            self.registry.release(src.sid)
            return
        try:
            connector = self.connectors.get(src.connector)
        except KeyError:
            self.dead_letters.publish(msg, reason="unknown_connector")
            self.registry.mark_failed(src.sid, self.now)
            return
        cursor = self._cursor_cls(etag=src.etag,
                                  last_modified=src.last_modified,
                                  position=src.position)
        # one trace root per fetched source (sampled; a no-op context
        # when tracing is off): ingest.fetch -> pipeline.process ->
        # store.append -> delivery.emit read back as one trace, and
        # accepted docs carry the trace_id so the asynchronous
        # delivery.write (TracingSink) joins the same trace later
        with self.tracer.span(          # positional: the hottest call
                "ingest.fetch", None,
                {"sid": src.sid, "channel": src.channel,
                 "connector": src.connector},
                False) as root:          # stack-free root: children ride
                                         # .event(), nothing nests deeper
            t0 = time.perf_counter()
            try:
                res = connector.fetch(src, cursor, self.now)
            except Exception as exc:  # connector fault -> backoff, not crash
                dt_fetch = time.perf_counter() - t0
                self._m_fetch_seconds.observe(dt_fetch,
                                              connector=src.connector)
                if self.latency is not None:
                    self.latency.observe_plane("ingest.fetch", dt_fetch)
                root.set("error", type(exc).__name__)
                self.metrics.fetch_errors_total += 1
                self._note_fetch(src.connector, error=True)
                self.dead_letters.publish(
                    {"sid": src.sid, "connector": src.connector,
                     "error": repr(exc)},
                    reason="connector_error")
                self.registry.mark_failed(src.sid, self.now)
                return
            dt_fetch = time.perf_counter() - t0
            self._m_fetch_seconds.observe(dt_fetch, connector=src.connector)
            lat = self.latency
            if lat is not None:
                lat.observe_plane("ingest.fetch", dt_fetch)
            self.metrics.fetched_total += 1
            # back-pressure gauges track what the hint actually DEFERS
            # beyond the source's own cadence (a hint <= interval_s applies
            # zero extra delay — max(interval, hint) — and must not read as
            # phantom back-pressure on the operator surfaces)
            deferred = None
            if res.backoff_hint_s is not None:
                deferred = max(0.0, res.backoff_hint_s - src.interval_s)
            self._note_fetch(src.connector, items=len(res.items),
                             not_modified=res.status == NOT_MODIFIED,
                             deferred_s=deferred)
            root.set("status", res.status)
            root.set("items", len(res.items))
            if res.status == NOT_MODIFIED:
                self.metrics.not_modified_total += 1
                # a 429-style hint can ride a NOT_MODIFIED (rate limiter)
                self.registry.mark_processed(src.sid, self.now,
                                             etag=res.etag,
                                             position=res.position,
                                             backoff_hint_s=res.backoff_hint_s)
                return
            if res.redirected_from:
                self.metrics.redirects_total += 1      # follow the hop
            accepted = 0
            out_batch = []
            trace_id = root.trace_id
            now_v = self.now
            skews = [] if lat is not None else None
            # leaf stages land as span EVENTS on the fetch root — tuple
            # appends materialized as child spans on read (cheap path);
            # a raise mid-stage is captured on the root by its __exit__
            t0 = time.perf_counter()
            for item in res.items:
                if item.malformed:
                    self.metrics.malformed_total += 1
                    self.dead_letters.publish(item,
                                              reason="malformed_item")
                    continue
                h = content_hash(item.guid)
                if self.dedup.seen_before(h):
                    self.metrics.duplicates_total += 1
                    continue
                doc = {"title": item.title, "body": item.body,
                       "published_at": item.published_at, "sid": src.sid,
                       "channel": src.channel}
                if item.extra:   # structured connector payload
                    doc.update(item.extra)
                if trace_id is not None:
                    doc["trace"] = trace_id
                # ingest-time stamp (virtual clock): the LatencySink
                # measures end-to-end latency from this when the
                # delivery write lands; the stamp rides into the
                # EventLog, so replayed records measure their true
                # (outage-inclusive) latency too
                doc["ingested_at"] = now_v
                if skews is not None and item.published_at is not None:
                    skews.append(now_v - item.published_at)
                out_batch.append((item.guid, doc))
                if self.item_hook is not None:
                    self.item_hook(doc)
                if self.analytics is not None:
                    self.analytics.observe(doc, now=self.now)
                accepted += 1
            root.event("pipeline.process", t0, {"accepted": accepted})
            if lat is not None:
                lat.observe_plane("pipeline.process",
                                  time.perf_counter() - t0)
                if skews:
                    lat.observe_freshness(src.channel, skews)
            if out_batch:
                n_out = len(out_batch)
                if self.store is not None:   # tee into the durable log
                    t0 = time.perf_counter()
                    self.store.append_documents(out_batch)
                    root.event("store.append", t0, {"records": n_out})
                    if lat is not None:
                        lat.observe_plane("store.append",
                                          time.perf_counter() - t0)
                # no span here: the delivery plane is covered by the
                # TracingSink's delivery.write at the moment the write
                # actually lands (inside the retry envelope)
                self.delivery.emit(out_batch)
            self.metrics.indexed_total += accepted
            self.registry.mark_processed(
                src.sid, self.now, etag=res.etag,
                last_modified=res.last_modified,
                position=res.position, backoff_hint_s=res.backoff_hint_s)
            for r in self.routers:
                r.on_processed()

    def _note_fetch(self, connector: str, *, items: int = 0,
                    not_modified: bool = False, error: bool = False,
                    deferred_s: Optional[float] = None) -> None:
        """Per-connector fetch-rate + back-pressure accounting, written
        natively into the metrics registry (``connector_stats()`` is a
        view over it; ``Metrics.ingest`` the flush-time snapshot).
        ``deferred_s`` is the EXTRA delay the hint added on top of the
        source's interval; only a positive deferral counts as a
        backoff."""
        self._m_fetches.inc(1, connector=connector)
        if items:
            self._m_items.inc(items, connector=connector)
        if not_modified:
            self._m_not_modified.inc(1, connector=connector)
        if error:
            self._m_fetch_errors.inc(1, connector=connector)
        if deferred_s is not None and deferred_s > 0.0:
            self._m_backoffs.inc(1, connector=connector)
            self._m_deferred.inc(float(deferred_s), connector=connector)

    # ---- runtime control API (repro.ingest) --------------------------------
    def register_channel(self, name: str) -> bool:
        """Open a channel at runtime: create its {main, priority} queue
        pair, register it with the distributor, mount a FeedRouter, and
        re-split the global optimal buffer across all routers.  Returns
        False if the channel already exists."""
        if name in self.distributor.main_queues:
            return False
        cfg = self.cfg
        main_q = BoundedPriorityQueue(cfg.queue_capacity,
                                      dead_letters=self.dead_letters)
        prio_q = BoundedPriorityQueue(cfg.queue_capacity,
                                      dead_letters=self.dead_letters)
        self.distributor.register_channel(name, main_q, prio_q)
        self.routers.append(FeedRouter(
            main_q, prio_q, self.mailbox,
            optimal_size=cfg.optimal_buffer,
            replenish_after=cfg.replenish_after,
            replenish_timeout_s=cfg.replenish_timeout_s,
            channel=name))
        per_router = max(1, cfg.optimal_buffer // len(self.routers))
        for r in self.routers:
            r.set_optimal_size(per_router)
        return True

    def channels(self) -> tuple:
        return self.distributor.channels()

    def register_connector(self, connector, name: Optional[str] = None) -> str:
        """Mount a Connector implementation; sources reference it by the
        returned name (``add_source(..., connector=name)``)."""
        return self.connectors.register(connector, name)

    def add_source(self, channel: str, *, url: str = "",
                   interval_s: Optional[float] = None, priority: int = 1,
                   first_due: Optional[float] = None, seed: int = 0,
                   connector: str = "sim", prioritize: bool = False) -> int:
        """Incrementally add a source while the pipeline runs (the
        paper's key flexibility claim).  Auto-registers the channel;
        fails fast on an unregistered connector.  ``first_due`` defaults
        to the current virtual time; ``prioritize`` front-runs the next
        tick (PriorityStreamsActor)."""
        if connector not in self.connectors:
            raise KeyError(
                f"unknown connector {connector!r}; registered: "
                f"{self.connectors.names()}")
        self.register_channel(channel)
        sid = self.registry.add_source(
            channel, url=url,
            interval_s=(self.cfg.feed_interval_s if interval_s is None
                        else interval_s),
            priority=priority,
            first_due=self.now if first_due is None else first_due,
            seed=seed, connector=connector)
        if prioritize:
            self.registry.prioritize(sid, self.now)
        return sid

    def remove_source(self, sid: int) -> bool:
        src = self.registry.get(sid)
        removed = self.registry.remove_source(sid)
        if removed and src is not None and src.connector in self.connectors:
            # a push-capable connector may hold buffered docs for this
            # source; discard them (dead-lettered) or they strand forever
            connector = self.connectors.get(src.connector)
            if hasattr(connector, "discard"):
                connector.discard(sid)
        return removed

    def pause(self, sid: int) -> bool:
        """Park a source: it stays registered but is skipped by the
        picker until ``resume``."""
        return self.registry.pause(sid)

    def resume(self, sid: int) -> bool:
        return self.registry.resume(sid)

    def list_sources(self, *, channel: Optional[str] = None) -> List[dict]:
        """Describe every registered source (sid, channel, connector,
        status, paused, cursor fields...), optionally filtered by
        channel."""
        out = self.registry.describe()
        if channel is not None:
            out = [d for d in out if d["channel"] == channel]
        return out

    def push(self, sid: int, docs: list) -> int:
        """Push-style ingress: hand documents to source ``sid``'s
        PushConnector and prioritize the source so they drain on the
        next scheduler tick, not a full feed interval later."""
        src = self.registry.get(sid)
        if src is None:
            raise KeyError(f"no source {sid}")
        connector = self.connectors.get(src.connector)
        if not hasattr(connector, "push"):
            raise TypeError(
                f"source {sid} uses connector {src.connector!r}, which is "
                f"not push-capable")
        accepted = connector.push(sid, docs, now=self.now)
        self.registry.prioritize(sid, self.now)
        return accepted

    # ---- virtual-time drive ------------------------------------------------
    def step(self, dt: float = 1.0, per_worker: int = 4) -> dict:
        self.now += dt
        with self.tracer.span("scheduler.tick",
                              attrs={"t": self.now}) as tick:
            picked = self.scheduler.maybe_tick(self.now)
            tick.set("picked", picked)
        pulled_box = [0]

        def replenish(now):
            pulled_box[0] += sum(r.maybe_replenish(now) for r in self.routers)

        done = self.pool.step(self.now, per_worker=per_worker,
                              replenish=replenish)
        pulled = pulled_box[0]
        # drive the delivery layer's virtual clock: time-based batch
        # flushes and retry backoff both key off this tick (counters in
        # Metrics.delivery refresh at flush_delivery / run_for cutoff,
        # not per step — call delivery_stats() for a live view)
        self.delivery.tick(self.now)
        if self.store is not None:
            self.store.tick(self.now)
            if self.cfg.replay_auto:
                self._maybe_replay()
        if picked:
            self.metrics.sent.append((self.now, picked))
        if done:
            self.metrics.received.append((self.now, done))
            self.metrics.deleted.append((self.now, done))
        alerts_fired = 0
        if self.analytics is not None:
            with self.tracer.span("window.advance") as adv:
                fired = self.analytics.advance(self.now)
                adv.set("alerts", len(fired))
            alerts_fired = len(fired)
            self.metrics.alerts_total += alerts_fired
            self.metrics.windows_closed_total = self.analytics.closed_total
        # SLO plane: pull sampled indicators + refresh burn gauges at the
        # engine's virtual cadence (deterministic; no-op between samples)
        if self.slo is not None:
            self.slo.maybe_sample(self.now)
        # dispatcher flow-control symptoms, sampled into histograms at
        # the same cadence (the point-in-time gauges only show the last
        # scrape; the histograms keep the whole depth distribution)
        if (self.latency is not None and self.cfg.delivery_dispatch
                and self.now - self._last_dispatch_sample
                >= self.cfg.slo_sample_interval_s):
            self._last_dispatch_sample = self.now
            for key, st in self.fan_out.backend_stats().items():
                if "queue_depth" in st:
                    self._h_dispatch_depth.observe(
                        st["queue_depth"], backend=key)
                    self._h_dispatch_handoff.observe(
                        st["handoff_p99_ms"], backend=key)
        return {"picked": picked, "pulled": pulled, "done": done,
                "backlog": sum(len(q) for q in self.main_queues.values()),
                "mailbox": len(self.mailbox), "pool": self.pool.size,
                "alerts": alerts_fired}

    def run_for(self, seconds: float, dt: float = 1.0, per_worker: int = 4):
        end = self.now + seconds
        while self.now < end:
            self.step(dt, per_worker=per_worker)
        self.flush_delivery()
        return self.metrics

    # ---- durability plane (repro.store) -------------------------------------
    def _maybe_replay(self) -> None:
        """Auto-replay: when a backend's per-sink health flips back to
        healthy, drain its ``delivery_failed:<backend>`` journal backlog
        through that backend's OWN retry envelope (part of the existing
        Batching -> FanOut -> Retrying stack), dedup-idempotently."""
        for b in self.fan_out.backends:
            name = b.terminal.name
            healthy = b.healthy
            was = self._backend_health.get(name, True)
            self._backend_health[name] = healthy
            if healthy and not was:
                # the replay engine verifies landing via the TERMINAL
                # sink's emitted-counter delta; under delivery_dispatch
                # the backend's dispatcher thread emits to that same
                # terminal asynchronously, so quiesce it first (queue
                # drained, dispatcher idle -> this thread is the only
                # emitter during the replay).  A backend that cannot
                # drain is not ready to take its backlog anyway — leave
                # the flip recorded and let a later round replay.
                drain = getattr(b, "drain", None)
                if callable(drain) and not drain():
                    self._backend_health[name] = was   # retry the flip
                    continue
                with self.tracer.span("replay.dead_letters",
                                      attrs={"backend": name}) as rsp:
                    res = self.store.replay.replay_dead_letters(
                        f"delivery_failed:{name}", b,
                        batch=self.cfg.replay_batch)
                    rsp.set("replayed", res["replayed"])
                self.metrics.replayed_total += res["replayed"]
                if res.get("stopped_early"):
                    # a replay batch failed to land (e.g. one transient
                    # write error) and the backlog is only partly
                    # drained.  A transient failure does NOT make the
                    # backend unhealthy, so without re-arming the flip
                    # here the residue would sit in the journal until
                    # the next full down/up cycle — potentially forever
                    self._backend_health[name] = was   # retry the flip


    def replay_status(self) -> dict:
        """Replay-engine + journal status (``{"enabled": False}`` when no
        store plane is mounted)."""
        if self.store is None:
            return {"enabled": False}
        return {"enabled": True, **self.store.replay.status()}

    def store_stats(self) -> dict:
        """Live durability-plane counters (appended/replayed/pending
        records, bytes, segments); ``Metrics.store`` holds the snapshot
        taken at the last ``flush_delivery``."""
        return {} if self.store is None else self.store.status()

    # ---- query/serving plane (repro.query) ----------------------------------
    def query_stats(self) -> dict:
        """Live query-plane counters (queries, cache hits/misses, stale
        rejections, cold scans, hot segment/watermark state);
        ``Metrics.query`` holds the snapshot taken at the last
        ``flush_delivery``."""
        return {} if self.query is None else self.query.status()

    def query_status(self) -> dict:
        """Query-plane status (``{"enabled": False}`` when
        ``cfg.query`` is off)."""
        if self.query is None:
            return {"enabled": False}
        return {"enabled": True, **self.query.status()}

    # ---- SLO / latency plane (repro.obs.slo, repro.obs.latency) -------------
    def _slo_sample(self, now: float):
        """Sampled SLO indicators, pulled by the engine at its virtual
        cadence: per-channel watermark lag, query-plane serving
        staleness, and the delivery success ratio (delta of
        terminal-accepted vs dead-lettered records since the last
        sample)."""
        out = []
        if self.latency is not None:
            for channel, t in self.latency._max_event_time.items():
                out.append(("watermark_lag", max(0.0, now - t),
                            {"channel": channel}))
        if self.query is not None:
            wm = self.query.status()["watermark"]
            if wm != float("-inf"):
                out.append(("query_staleness", max(0.0, now - wm), {}))
        good = bad = 0.0
        for st in self.fan_out.backend_stats().values():
            good += st["terminal_emitted"]
            bad += st["dead_lettered"]
        pg, pb = self._slo_delivery_prev
        self._slo_delivery_prev = (good, bad)
        dg, db = int(good - pg), int(bad - pb)
        if dg or db:
            out.append(("delivery_success_ratio", dg, db, {}))
        return out

    def slo_status(self) -> dict:
        """SLO error budgets + multi-window burn rates per spec
        (``{"enabled": False}`` when ``cfg.slos`` is empty)."""
        if self.slo is None:
            return {"enabled": False}
        return self.slo.status(self.now)

    def latency_status(self) -> dict:
        """Always-on latency plane summary: per-plane hop histograms
        plus the end-to-end fetch-to-delivered series
        (``{"enabled": False}`` when ``cfg.latency_tracking`` is off)."""
        if self.latency is None:
            return {"enabled": False}
        lt = self.latency
        planes = {labels["plane"]: lt.plane.summary(**labels)
                  for labels, _ in lt.plane.items()}
        e2e = [{"labels": labels, **lt.e2e.summary(**labels)}
               for labels, _ in lt.e2e.items()]
        return {"enabled": True, "planes": planes, "e2e": e2e}

    def close(self) -> None:
        """Flush delivery and close the durability plane (fsyncs the
        active log segments so a reopen sees every appended record) and
        the observability plane (flushes the span exporter)."""
        self.flush_delivery()
        if self.store is not None:
            self.store.close()
        self.obs.close()

    def flush_delivery(self) -> None:
        """Force buffered/parked records out to every backend and refresh
        the delivery counters (run_for does this at its cutoff so sinks
        are complete up to ``now``).  With a store plane + analytics
        mounted, the journal's ``late_event`` backlog is drained through
        the batch path here too — late data joins the same rule state
        instead of rotting on disk (sessions excluded: the batch path
        cuts late events into sessions of their own, and merging them
        into sessions already closed is another semantics)."""
        if (self.store is not None and self.analytics is not None
                and self.cfg.replay_late_on_flush
                and self.analytics.operator.spec.kind != "session"):
            with self.tracer.span("replay.late_events") as rsp:
                res = self.store.replay.replay_late_events(
                    watermark=self.now)
                rsp.set("alerts", res["alerts"])
            self.metrics.alerts_total += res["alerts"]
        self.delivery.flush()
        if self.store is not None and self.cfg.replay_auto:
            # a drain can complete a backend's recovery (its first
            # successful write may happen inside the flush, especially
            # under delivery_dispatch where delivery is asynchronous) —
            # observe the flip here too, then drain the replay traffic
            before = self.metrics.replayed_total
            self._maybe_replay()
            if self.metrics.replayed_total != before:
                self.delivery.flush()
        self.metrics.delivery = self.delivery_stats()
        self.metrics.store = self.store_stats()
        self.metrics.ingest = self.connector_stats()
        self.metrics.query = self.query_stats()
        self.metrics.slo = ({} if self.slo is None
                            else self.slo.status(self.now))

    def connector_stats(self) -> dict:
        """Live per-connector ingress counters: fetches, items,
        not_modified, errors, and back-pressure (backoffs applied +
        total deferred seconds).  A view assembled from the metrics
        registry — repro.obs owns the one copy of these numbers.
        ``Metrics.ingest`` holds the snapshot taken at the last
        ``flush_delivery``."""
        columns = (("fetches", self._m_fetches),
                   ("items", self._m_items),
                   ("not_modified", self._m_not_modified),
                   ("errors", self._m_fetch_errors),
                   ("backoffs", self._m_backoffs),
                   ("deferred_s", self._m_deferred))
        out: Dict[str, Dict[str, float]] = {}
        for key, counter in columns:
            for labels, value in counter.items():
                st = out.setdefault(labels.get("connector", ""), {
                    "fetches": 0, "items": 0, "not_modified": 0,
                    "errors": 0, "backoffs": 0, "deferred_s": 0.0})
                st[key] = value if key == "deferred_s" else int(value)
        return out

    # ---- observability plane (repro.obs) ------------------------------------
    def _sync_registry(self) -> None:
        """Collector: adopt every externally-tracked total into the
        registry (``Counter.sync`` is set-to-max, so re-running is
        idempotent).  Registered with ``add_collector`` so it runs right
        before every ``snapshot()`` / ``render_prometheus()`` / selfmon
        sample — exposition is always whole without per-event cost."""
        reg = self.obs.metrics
        m = self.metrics
        c, g = reg.counter, reg.gauge
        c("docs_indexed_total",
          "documents accepted and handed to delivery").sync(m.indexed_total)
        c("docs_duplicates_total",
          "items dropped by the dedup window").sync(m.duplicates_total)
        c("docs_malformed_total",
          "items dead-lettered as malformed").sync(m.malformed_total)
        c("redirects_total", "fetches that followed a redirect hop").sync(
            m.redirects_total)
        c("alerts_fired_total", "alerts fired by the rule engine").sync(
            m.alerts_total)
        c("windows_closed_total", "event-time windows closed").sync(
            m.windows_closed_total)
        c("replayed_records_total",
          "records re-delivered from the journal").sync(m.replayed_total)
        c("scheduler_picked_total", "sources picked by the cron").sync(
            self.scheduler.picked_total)
        c("scheduler_requeued_total", "expired leases requeued").sync(
            self.scheduler.requeued_total)
        c("unroutable_total",
          "picks dead-lettered for an unopened channel").sync(
            self.distributor.unroutable)
        g("pool_size", "current worker-pool size").set(self.pool.size)
        g("mailbox_depth", "messages parked in the worker mailbox").set(
            len(self.mailbox))
        g("channel_backlog", "messages queued across channel queues").set(
            sum(len(q) for q in self.main_queues.values()))
        dl = self.dead_letters.snapshot()
        dlc = c("dead_letters_total",
                "dead-lettered records by taxonomy reason")
        for reason, n in dl["by_reason"].items():
            dlc.sync(n, reason=reason)
        # delivery layer, one series set per backend
        for key, st in self.fan_out.backend_stats().items():
            c("delivery_emitted_total",
              "records accepted by the terminal sink").sync(
                st["terminal_emitted"], backend=key)
            c("delivery_retried_total", "re-delivery attempts").sync(
                st["retried"], backend=key)
            c("delivery_dead_lettered_total",
              "records given up on after retries").sync(
                st["dead_lettered"], backend=key)
            g("delivery_lag",
              "records emitted to the fan-out but not yet accepted by "
              "this backend's terminal").set(st["lag"], backend=key)
            g("delivery_healthy", "1 = backend healthy, 0 = failing").set(
                1.0 if st["healthy"] else 0.0, backend=key)
            g("delivery_pending_retry",
              "records parked awaiting retry backoff").set(
                st.get("pending_retry", 0), backend=key)
            if "queue_depth" in st:        # dispatching backend
                g("dispatch_queue_depth",
                  "batches waiting in the hand-off queue").set(
                    st["queue_depth"], backend=key)
                g("dispatch_handoff_p99_ms",
                  "p99 hand-off queue wait").set(
                    st["handoff_p99_ms"], backend=key)
                c("dispatch_dropped_total",
                  "batches dead-lettered on hand-off overflow").sync(
                    st["dropped"], backend=key)
        if self.store is not None:
            st = self.store.status()
            c("store_appended_records_total",
              "records appended to the event log").sync(
                st["appended_records"])
            c("store_appended_bytes_total",
              "bytes appended to the event log").sync(st["appended_bytes"])
            g("store_segments", "sealed event-log segments").set(
                st["segments"])
            c("store_journal_records_total",
              "records appended to the dead-letter journal").sync(
                st["journal_records"])
            g("store_pending_replay_records",
              "journaled records awaiting replay").set(
                st["pending_replay_records"])
            if "columnar" in st:
                col = st["columnar"]
                c("store_columnar_sealed_segments_total",
                  "JSON tails sealed into columnar segments").sync(
                    col["sealed_columnar_segments"])
                c("store_compactions_total",
                  "keyed-compaction passes committed").sync(
                    col["compactions"])
                c("store_compacted_records_dropped_total",
                  "records dropped as superseded by keyed compaction"
                  ).sync(col["compacted_records_dropped"])
                c("store_offloaded_segments_total",
                  "sealed segments moved to the object store").sync(
                    col["offloaded_segments"])
                c("store_cold_fetches_total",
                  "offloaded segments fetched back for a scan").sync(
                    col["cold_fetches"])
                c("store_cold_fetch_failures_total",
                  "cold fetches that failed and were skipped").sync(
                    col["cold_fetch_failures"])
                c("store_blocks_pruned_total",
                  "columnar blocks skipped via min/max block stats").sync(
                    col["blocks_pruned"])
                g("store_cold_segments",
                  "sealed segments currently offloaded").set(
                    col["cold_segments"])
            # replay-chain breakdown (StageProfiler): which stage eats
            # the batch-replay time, visible in every scrape, not just
            # replay_status()["profile"]; the shares are of the top-level
            # stages, so they add up to 1
            for stage, ps in self.store.replay.profiler.snapshot().items():
                if "." not in stage:
                    g("replay_stage_share",
                      "fraction of profiled replay wall-clock per "
                      "top-level stage").set(ps["share"], stage=stage)
                g("replay_stage_mean_ms",
                  "mean wall-clock per replay-stage pass").set(
                    ps["mean_ms"], stage=stage)
                c("replay_stage_calls_total",
                  "passes through each replay stage").sync(
                    ps["calls"], stage=stage)
                c("replay_stage_ms_total",
                  "total wall-clock milliseconds per replay stage").sync(
                    ps["total_ms"], stage=stage)
        # device launches per kernel and route (replay / drain / query),
        # process-wide like the kernels' executables
        for kernel, routes in kernel_launches().items():
            for route, kc in routes.items():
                c("kernel_launches_total",
                  "kernel launches per route").sync(
                    kc["launches"], kernel=kernel, route=route)
                c("kernel_new_shapes_total",
                  "kernel launches at a static shape not launched "
                  "before in this process").sync(
                    kc["new_shapes"], kernel=kernel, route=route)
        for path, n in pack_slot_index().items():
            c("pack_slot_index_total",
              "column packs per slot-index path (dense table, sort "
              "or session layout)").sync(n, path=path)
        for order, n in unpack_order().items():
            c("unpack_order_total",
              "unpacks of the kernel's lanes by record order (the "
              "pack's slot order, or sorted by window end and key)").sync(
                n, order=order)
        for cut, n in pack_sessions().items():
            c("pack_sessions_total",
              "sessions laid out by session packs, by what opened "
              "them (a new key or a gap)").sync(n, cut=cut)
        for layout, n in columnar_blocks_decoded().items():
            c("columnar_blocks_decoded_total",
              "columnar store blocks read and checksummed, by where "
              "their string dictionaries lie (payload or header)").sync(
                n, layout=layout)
        if self.query is not None:
            qs = self.query.status()
            c("query_queries_total",
              "aggregate queries answered or refused").sync(qs["queries"])
            c("query_cache_hits_total",
              "queries served from the watermark-invalidated cache").sync(
                qs["cache_hits"])
            c("query_cache_misses_total",
              "queries that recomputed their aggregation").sync(
                qs["cache_misses"])
            c("query_stale_rejected_total",
              "queries refused for exceeding the staleness bound").sync(
                qs["stale_rejected"])
            c("query_cold_scans_total",
              "queries that replayed the event log for cold ranges").sync(
                qs["cold_scans"])
            g("query_hot_segments",
              "materialized (key, window) aggregate segments").set(
                qs["hot_segments"])
            g("query_cache_entries", "live result-cache entries").set(
                qs["cache_entries"])
        ts = self.tracer.status()
        g("trace_flight_spans",
          "finished spans retained in the flight recorder").set(
            ts["flight_spans"])
        c("trace_finished_spans_total", "spans finished since start").sync(
            ts["finished_spans"])

    def metrics_text(self) -> str:
        """Prometheus text exposition of the whole platform (runs the
        collectors first, so the scrape is current)."""
        return self.obs.metrics.render_prometheus()

    def metrics_snapshot(self) -> dict:
        """json-safe registry dump (counters/gauges/histograms)."""
        return self.obs.metrics.snapshot()

    def obs_status(self) -> dict:
        """Observability-plane status: tracer counters + registered
        metric names + self-monitoring state."""
        out = self.obs.status()
        out["selfmon"] = (None if self.selfmon is None
                          else {"sid": self.selfmon_sid,
                                "samples": self.selfmon.samples})
        return out

    def trace(self, trace_id: str) -> list:
        """Every retained span of one trace, start-ordered (the flight
        recorder's reconstruction surface)."""
        return self.tracer.trace(trace_id)

    def delivery_stats(self) -> dict:
        """Per-backend delivery counters: emitted (records the terminal
        sink accepted), retried, dead_lettered, lag, healthy — plus,
        under ``delivery_dispatch``, the flow-control gauges
        queue_depth / handoff_p50_ms / handoff_p99_ms / dropped."""
        out = {"emitted": self.delivery.counters.emitted,
               "pending": getattr(self.delivery, "pending", 0),
               "backends": {}}
        for key, st in self.fan_out.backend_stats().items():
            entry = {
                "emitted": st["terminal_emitted"],
                "retried": st["retried"],
                "dead_lettered": st["dead_lettered"],
                "pending_retry": st.get("pending_retry", 0),
                "lag": st["lag"],
                "healthy": st["healthy"],
            }
            if "queue_depth" in st:        # dispatching backend
                for k in ("queue_depth", "dropped",
                          "handoff_p50_ms", "handoff_p99_ms"):
                    entry[k] = st[k]
            out["backends"][key] = entry
        return out

    @property
    def alerts(self) -> list:
        """Alert records fired by the analytics stage (empty when off)."""
        return [] if self.analytics is None else self.analytics.alerts

    # ---- fault tolerance ----------------------------------------------------
    def snapshot(self) -> dict:
        return {"now": self.now, "registry": self.registry.snapshot()}

    def restore_registry(self, snap: dict) -> None:
        """Accepts snapshots from either registry flavour (the sharded
        format is a superset of the seed's single-registry one).
        Channels the snapshot references are re-registered: a runtime-
        added channel must come back with its queues/router, or its
        restored sources would dead-letter as unknown_channel forever."""
        self.now = snap["now"]
        self.registry = _ingest().ShardedStreamRegistry.restore(
            snap["registry"], shards=self.cfg.registry_shards)
        self.scheduler.registry = self.registry
        for d in snap["registry"]["sources"]:
            self.register_channel(d["channel"])
