"""BrokerNode: the application assembly — config → running broker.

Behavioral reference: ``emqx_app:start`` / ``emqx_sup`` boot order [U]
(SURVEY.md §3.1): config load → cluster substrate → core workers
(hooks/metrics/router/broker/cm/sys) → dependent services (auth, retainer,
delayed, rewrite, rule engine) → listeners last, so no client connects to a
half-booted node.  Stop order is the reverse.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import Any, Dict, List, Optional

from .auth import AuthChain, Authz, attach_auth
from .broker import Broker
from .broker.banned import Banned
from .broker.channel import Channel
from .broker.cm import ConnectionManager
from .broker.flapping import Flapping
from .broker.limiter import LimiterGroup
from .config import Config
from .observe import heap
from .observe.wiring import observe
from .rule_engine import RuleEngine
from .services.auto_subscribe import AutoSubscribe
from .services.delayed import DelayedPublish
from .services.retainer import Retainer
from .services.rewrite import TopicRewrite
from .transport.connection import ConnInfo, Connection
from .transport.listener import Listener, Listeners

log = logging.getLogger(__name__)


#: where the persistent compilation cache lives when nothing outside
#: places it: one fixed path inside the checkout (the path is part of
#: the cache key's lookup — a directory that moves never hits)
XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_xla_cache() -> bool:
    """Turn JAX's persistent compilation cache on: a process restart
    finds every previously-compiled serve executable on disk, so even
    the FIRST cold-start compile is a cache hit instead of an XLA run.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside — JAX reads the variable itself and this sets NO directory;
    otherwise it is :data:`XLA_CACHE_DIR`.  Returns True when the cache
    is active; best-effort — a jax without the knobs (or no jax at all)
    degrades to in-memory compiles, never a startup failure."""
    try:
        import jax
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.makedirs(XLA_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    except Exception:
        log.exception("persistent XLA compilation cache unavailable; "
                      "cold-start compiles stay in-memory")
        return False
    # cache every executable however fast its compile was (the serve
    # kernels are small; the default min-time floor would skip them) —
    # tuning knobs are advisory, absence is not an error
    for knob, value in (
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
        ("jax_persistent_cache_min_entry_size_bytes", -1),
    ):
        try:
            jax.config.update(knob, value)
        except Exception:  # noqa: PERF203 — per-knob isolation
            log.debug("XLA cache knob %s unsupported by this jax",
                      knob, exc_info=True)
    return True

__all__ = ["BrokerNode"]


class BrokerNode:
    """One broker node: all subsystems wired, listeners optional.

    Synchronous parts (broker/session/services) work immediately after
    construction; ``await start()`` brings up listeners and periodic jobs.
    """

    def __init__(
        self,
        config: Optional[Config] = None,
        auth_chain: Optional[AuthChain] = None,
        authz: Optional[Authz] = None,
    ) -> None:
        self.config = config if config is not None else Config()
        cfg = self.config
        self.node_name = cfg.get("node.name")
        self.broker = Broker(
            node=self.node_name,
            shared_strategy=cfg.get("broker.shared_subscription_strategy"),
            session_defaults={
                "max_inflight": cfg.get("mqtt.max_inflight"),
                "max_mqueue_len": cfg.get("mqtt.max_mqueue_len"),
                "expiry_interval": cfg.get("mqtt.session_expiry_interval"),
                "max_awaiting_rel": cfg.get("mqtt.max_awaiting_rel"),
            },
        )
        self.cm = ConnectionManager(self.broker)
        self.observed = observe(
            self.broker, sys_interval=cfg.get("broker.sys_msg_interval")
        )
        # supervision tree (supervise.py): every long-lived background
        # task (fanout drain, cluster loops, bridge workers, gateway
        # retry, exhook senders, telemetry/statsd, housekeeping)
        # registers here; restart intensity escalates to an alarm +
        # degraded mode instead of dying
        from .broker.olp import Olp
        from .supervise import Supervisor

        self.supervisor = Supervisor(
            metrics=self.observed.metrics,
            alarms=self.observed.alarms,
            max_restarts=cfg.get("supervisor.max_restarts"),
            window_s=cfg.get("supervisor.window"),
            backoff_base=cfg.get("supervisor.backoff_base"),
            backoff_max=cfg.get("supervisor.backoff_max"),
        )
        self.olp = Olp(
            alarms=self.observed.alarms,
            max_loop_lag=cfg.get("overload_protection.max_loop_lag"),
            max_queue_depth=cfg.get("overload_protection.max_queue_depth"),
            cooloff=cfg.get("overload_protection.cooloff"),
        )
        # connection gauges come from the CM (a node-level table), so
        # they wire here rather than in observe(broker)
        self.observed.stats.provide(
            "connections.count", self.cm.total_connection_count)
        self.observed.stats.provide(
            "live_connections.count", self.cm.connection_count)
        self.banned = Banned().attach(self.broker)
        self.flapping = Flapping(
            self.banned,
            max_count=cfg.get("flapping_detect.max_count"),
            window_time=cfg.get("flapping_detect.window_time"),
            ban_time=cfg.get("flapping_detect.ban_time"),
            enable=cfg.get("flapping_detect.enable"),
        ).attach(self.broker)
        # batched admission plane (broker/admission.py): per-client
        # behavior features scored in one vectorized pass per tick by
        # the supervised admission.score child, feeding the quarantine
        # ladder (throttle via the client's TokenBucket, QoS0-shed,
        # temp-ban via Banned).  Off keeps broker.admission None —
        # every seam stays one attr load + identity test.
        self.admission = None
        if cfg.get("admission.enable"):
            from .broker.admission import Admission

            self.admission = Admission(
                banned=self.banned,
                alarms=self.observed.alarms,
                metrics=self.observed.metrics,
                olp=self.olp,
                tick_s=cfg.get("admission.tick"),
                fan_window=cfg.get("admission.fan_window"),
                alpha=cfg.get("admission.alpha"),
                threshold=cfg.get("admission.threshold"),
                clear_ratio=cfg.get("admission.clear_ratio"),
                hold_ticks=cfg.get("admission.hold_ticks"),
                decay_ticks=cfg.get("admission.decay_ticks"),
                throttle_rate=cfg.get("admission.throttle_rate"),
                restore_rate=cfg.get("limiter.max_messages_rate"),
                ban_time=cfg.get("admission.ban_time"),
                idle_expiry=cfg.get("admission.idle_expiry"),
                max_connect_rate=cfg.get("admission.max_connect_rate"),
                max_malformed_rate=cfg.get(
                    "admission.max_malformed_rate"),
                max_auth_fail_rate=cfg.get(
                    "admission.max_auth_fail_rate"),
                max_publish_rate=cfg.get("admission.max_publish_rate"),
                max_publish_bytes_rate=cfg.get(
                    "admission.max_publish_bytes_rate"),
                max_topic_fan=cfg.get("admission.max_topic_fan"),
            ).attach(self.broker)
            self.admission.throttle_cb = self._admission_throttle
            self.admission.kick_cb = self.kick_client
        self.retainer = (
            Retainer(
                msg_expiry_interval=cfg.get("retainer.msg_expiry_interval"),
                max_payload_size=cfg.get("retainer.max_payload_size"),
                max_retained_messages=cfg.get("retainer.max_retained_messages"),
            ).attach(self.broker)
            if cfg.get("retainer.enable")
            else None
        )
        self.delayed = (
            DelayedPublish(
                max_delayed_messages=cfg.get("delayed.max_delayed_messages")
            ).attach(self.broker)
            if cfg.get("delayed.enable")
            else None
        )
        self.rewrite = TopicRewrite([]).attach(self.broker)
        self.auto_subscribe = AutoSubscribe()
        self.auto_subscribe.attach(self.broker)
        self.rule_engine = RuleEngine(self.broker)
        from .bridge import BridgeManager

        self.bridges = BridgeManager(self)
        self.access_control = None
        self._auth_confs: list = []    # REST-created authenticator confs
        self._authz_confs: list = []   # REST-created source confs
        if auth_chain is not None or authz is not None:
            self.access_control = attach_auth(
                self.broker,
                auth_chain if auth_chain is not None else AuthChain(
                    allow_anonymous=cfg.get("authn.allow_anonymous")),
                authz if authz is not None else Authz(
                    no_match=cfg.get("authz.no_match")
                ),
            )

        from .observe.trace import TraceManager

        self.tracing = TraceManager(self)
        # stage-level latency observatory: the main plane's histogram
        # set (None = every recording site is zero-call) + the
        # always-on flight recorder dumping into the TraceManager dir
        # on breaker trip / brownout escalation / supervisor_degraded /
        # the mgmt manual trigger
        from .observe.flightrec import FlightRecorder
        from .observe.hist import HistSet

        self.hists = HistSet("main") if cfg.get("obs.hist.enable") \
            else None
        # a main-loop connection's per-publish stage histograms,
        # resolved once here and handed to every protocol
        # make_protocol builds
        self._conn_hists = None
        if self.hists is not None:
            # sync publish path spans: traffic bypassing the fanout
            # pipeline (shape gate, fanout off) records into the same
            # deliver/flush/e2e histograms the batched drain writes
            self.broker.attach_hists(self.hists)
            self._conn_hists = (
                self.hists.hist("obs.stage.ingest_parse"),
                self.hists.hist("obs.stage.ingest_queue"),
                self.hists.hist("obs.stage.intercept"),
                self.hists.hist("obs.stage.handle_publish"),
                self.hists.hist("obs.stage.ack_in"))
        self.flightrec = FlightRecorder(
            self.tracing.dir,
            depth=cfg.get("obs.flightrec.depth"),
            metrics=self.observed.metrics,
        )
        # sleep-drift sampler: CPU saturation trips overload protection
        # even when no queue grows (started as a supervised child); while
        # it runs it also clocks the loop's busy and idle time
        from .broker.olp import LoopLagProbe

        self.lag_probe = None
        probe_interval = cfg.get("overload_protection.lag_probe_interval")
        if probe_interval and probe_interval > 0:
            self.lag_probe = LoopLagProbe(
                self.olp, metrics=self.observed.metrics,
                interval=probe_interval,
                hist=(self.hists.hist("obs.stage.loop_run")
                      if self.hists is not None else None),
                ring=self.flightrec.ring("loop"),
            )
        self.supervisor.flightrec = self.flightrec
        if self.admission is not None:
            # built above, before the recorder existed: escalation
            # dumps (reason admission_escalation) wire up here
            self.admission.flightrec = self.flightrec
        self.observed.sys.attach_hists(self.hist_percentiles)
        from .observe.slow_subs import SlowSubs
        from .plugins import PluginManager

        self.slow_subs = (
            SlowSubs(
                threshold_ms=cfg.get("slow_subs.threshold") * 1e3,
                top_k=cfg.get("slow_subs.top_k"),
                window_s=cfg.get("slow_subs.window_time"),
                max_ms=cfg.get("slow_subs.latency_ceiling") * 1e3,
            ).attach(self.broker)
            if cfg.get("slow_subs.enable") else None
        )
        from .observe.topic_metrics import TopicMetrics

        self.topic_metrics = TopicMetrics(
            max_topics=cfg.get("topic_metrics.max_topics")
        ).attach(self.broker)
        self.plugins = PluginManager(self)
        self.psk = None
        if cfg.get("psk.enable"):
            from .auth.psk import PskStore

            self.psk = PskStore(
                (cfg.get("psk.entries") or "").replace(",", "\n")
            )
        self.statsd = None
        self.telemetry = None
        self._attach_client_metrics()
        self._register_config_handlers()
        # session expiry: clientid -> disconnect time, swept by
        # housekeeping; must exist before restore so restored disconnected
        # sessions enter the expiry sweep immediately
        self._disconnected_at: Dict[str, float] = {}
        self.persistence = None
        data_dir = (cfg.get("node.data_dir") or "").strip()
        if data_dir:
            from .storage import Persistence

            self.persistence = Persistence(self, data_dir)
            self.persistence.restore()

        self.exhook = None  # built lazily in start() (needs a loop + grpc)
        self.ocsp_cache = None  # OCSP stapling cache (ssl listener)
        self.quic = None        # QUIC endpoint (quic listener)
        self.quic_port = 0
        self.cluster = None  # built lazily in start() (needs a loop)
        self.match_service = None  # in-process TPU matcher (start())
        self.fanout_pipeline = None  # batched publish fanout (start())
        self.mgmt = None
        self.mgmt_server = None
        self.gateways = None  # GatewayManager, built in start()
        self.dashboard_users = None  # DashboardUsers, built in _start_mgmt
        self.limiter = LimiterGroup(
            max_conn_rate=cfg.get("limiter.max_conn_rate"),
            max_messages_rate=cfg.get("limiter.max_messages_rate"),
            max_bytes_rate=cfg.get("limiter.max_bytes_rate"),
        )
        # hashed timer wheel (transport/timerwheel.py), part of the one
        # batched-stack opt-in: per-connection keepalive/retry ticks and
        # gateway sweeps ride coarse buckets — one scheduled callback
        # per tick regardless of connection count.  Flag off keeps the
        # PR-5 per-connection loop.call_later timers byte-for-byte.
        self.timer_wheel = None
        self.shard_pool = None  # connection-plane shards (start())
        if cfg.get("broker.fanout.enable"):
            from .transport.timerwheel import TimerWheel

            self.timer_wheel = TimerWheel()
        self.listeners = Listeners()
        self.connections: Dict[str, Connection] = {}  # clientid -> conn
        # every accepted connection, incl. pre-CONNECT ones — stop() must
        # be able to close sockets that never completed a handshake
        self._all_conns: set = set()
        self.broker.on_deliver = self._on_deliver
        self._jobs: List[Any] = []  # tasks or supervised Child handles
        self.started_at = time.time()
        self._running = False
        self._last_idle_sweep = time.monotonic()
        self._configure_listeners()

    # ------------------------------------------------------------------

    def _attach_client_metrics(self) -> None:
        m = self.observed.metrics
        hooks = self.broker.hooks
        hooks.add("client.connect",
                  lambda cid, pkt: m.inc("client.connect"),
                  name="metrics.client.connect")
        hooks.add("client.connected",
                  lambda cid, info: (m.inc("client.connected"),
                                     self._disconnected_at.pop(cid, None))[0],
                  name="metrics.client.connected")
        hooks.add("client.disconnected",
                  lambda cid, reason: (m.inc("client.disconnected"),
                                       self._mark_disconnected(cid))[0],
                  name="metrics.client.disconnected")
        hooks.add("client.subscribe",
                  lambda cid, pkt: m.inc("client.subscribe"),
                  name="metrics.client.subscribe")
        hooks.add("client.unsubscribe",
                  lambda cid, pkt: m.inc("client.unsubscribe"),
                  name="metrics.client.unsubscribe")

    def _register_config_handlers(self) -> None:
        """Hot-update plumbing (emqx_config_handler analog): push runtime
        config changes into the live components, so PUT /api/v5/configs
        actually takes effect (SURVEY.md §5.6)."""
        cfg = self.config
        cfg.on_update(
            "limiter.max_conn_rate",
            lambda p, o, n: self.limiter.reconfigure(max_conn_rate=n),
        )
        cfg.on_update(
            "limiter.max_messages_rate",
            lambda p, o, n: self.limiter.reconfigure(max_messages_rate=n),
        )
        cfg.on_update(
            "limiter.max_bytes_rate",
            lambda p, o, n: self.limiter.reconfigure(max_bytes_rate=n),
        )
        cfg.on_update(
            "mqtt.max_inflight",
            lambda p, o, n: self.broker.session_defaults.__setitem__(
                "max_inflight", n
            ),
        )
        cfg.on_update(
            "mqtt.max_mqueue_len",
            lambda p, o, n: self.broker.session_defaults.__setitem__(
                "max_mqueue_len", n
            ),
        )
        cfg.on_update(
            "broker.shared_subscription_strategy",
            lambda p, o, n: setattr(self.broker.shared, "strategy", n),
        )
        if self.retainer is not None:
            cfg.on_update(
                "retainer.msg_expiry_interval",
                lambda p, o, n: setattr(
                    self.retainer, "msg_expiry_interval", n
                ),
            )
        if self.delayed is not None:
            cfg.on_update(
                "delayed.max_delayed_messages",
                lambda p, o, n: setattr(
                    self.delayed, "max_delayed_messages", n
                ),
            )
        if self.access_control is not None:
            cfg.on_update(
                "authz.no_match",
                lambda p, o, n: setattr(
                    self.access_control.authz, "no_match", n
                ),
            )

    def _admission_throttle(self, clientid: str,
                            rate: Optional[float]) -> bool:
        """Admission-ladder level 1: retune the live connection's
        message TokenBucket IN PLACE (the proto holds a direct
        reference, so a dict swap would detach it).  ``rate`` None
        restores the configured limiter.max_messages_rate.  Shard-owned
        connections share the same bucket object; the retune is a pair
        of float stores — a racy read on the shard loop sees either
        rate, both valid (the gauge-not-invariant discipline)."""
        conn = self.connections.get(clientid)
        if conn is None:
            return False
        bucket = getattr(conn, "_msg_bucket", None)
        if bucket is None:
            return False
        if rate is None:
            restore = float(self.config.get("limiter.max_messages_rate"))
            bucket.retune(restore)
        else:
            bucket.retune(rate)
        return True

    def _mark_disconnected(self, clientid: str) -> None:
        sess = self.broker.sessions.get(clientid)
        if sess is not None:
            self._disconnected_at[clientid] = time.time()

    def _configure_listeners(self) -> None:
        cfg = self.config
        if cfg.get("listeners.tcp.default.enable"):
            self.listeners.add(
                Listener(
                    "default",
                    cfg.get("listeners.tcp.default.bind"),
                    self.handle_stream,
                    kind="tcp",
                    max_connections=cfg.get(
                        "listeners.tcp.default.max_connections"
                    ),
                    max_conn_rate=cfg.get("limiter.max_conn_rate"),
                    reuse_port=cfg.get("listeners.tcp.default.reuse_port"),
                    proto_factory=(
                        self.make_protocol
                        if cfg.get("listeners.tcp.default.fast_path")
                        else None
                    ),
                )
            )
        if cfg.get("listeners.ssl.default.enable"):
            ctx = self._build_ssl_context()
            if ctx is not None:
                self.listeners.add(
                    Listener(
                        "ssl-default",
                        cfg.get("listeners.ssl.default.bind"),
                        self.handle_stream,
                        kind="tcp",
                        ssl_context=ctx,
                    )
                )
        if cfg.get("listeners.ws.default.enable"):
            self.listeners.add(
                Listener(
                    "default",
                    cfg.get("listeners.ws.default.bind"),
                    self.handle_stream,
                    kind="ws",
                )
            )

    def _build_ssl_context(self):
        """Server TLS context for the ssl listener: certfile/keyfile,
        optional client-cert verification, optional PSK identities
        (gated on runtime support — SURVEY.md §2.4 posture)."""
        import ssl as _ssl

        cfg = self.config
        cert = (cfg.get("listeners.ssl.default.certfile") or "").strip()
        key = (cfg.get("listeners.ssl.default.keyfile") or "").strip()
        ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
        try:
            if cert:
                ctx.load_cert_chain(cert, key or None)
            elif self.psk is None:
                log.error("ssl listener enabled without certfile or psk")
                return None
            ca = (cfg.get("listeners.ssl.default.cacertfile") or "").strip()
            if ca:
                ctx.load_verify_locations(ca)
            if cfg.get("listeners.ssl.default.verify"):
                ctx.verify_mode = _ssl.CERT_REQUIRED
            crl = (cfg.get("listeners.ssl.default.crlfile") or "").strip()
            if crl:
                # revocation: load_verify_locations accepts CRL PEMs;
                # the flag decides leaf-only vs whole-chain checking.
                # A CRL without client-cert verification would be
                # silently inert (no cert is ever requested) — fail
                # closed by implying CERT_REQUIRED.
                ctx.load_verify_locations(cafile=crl)
                check = (cfg.get("listeners.ssl.default.crl_check")
                         or "leaf").strip().lower()
                if check not in ("leaf", "chain"):
                    # unknown value fails CLOSED (the stricter scope) —
                    # a typo must not silently weaken revocation
                    log.warning("unknown crl_check %r; using 'chain'",
                                check)
                    check = "chain"
                ctx.verify_flags |= (
                    _ssl.VERIFY_CRL_CHECK_CHAIN if check == "chain"
                    else _ssl.VERIFY_CRL_CHECK_LEAF)
                if ctx.verify_mode != _ssl.CERT_REQUIRED:
                    log.warning(
                        "crlfile set without verify=true; enabling "
                        "client-cert verification (CRL would otherwise "
                        "never be consulted)")
                    ctx.verify_mode = _ssl.CERT_REQUIRED
            if self.psk is not None:
                self.psk.wire_into(ctx)
            sni = (cfg.get("listeners.ssl.default.sni") or "").strip()
            if sni:
                # per-hostname contexts: "host=cert.pem;key.pem" list
                by_host = {}
                for entry in sni.split(","):
                    entry = entry.strip()
                    if not entry:
                        continue  # trailing comma etc.
                    host_part, eq, files = entry.partition("=")
                    c, _, k = files.partition(";")
                    if not eq or not c.strip():
                        log.warning("ignoring bad sni entry %r", entry)
                        continue
                    hctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
                    hctx.load_cert_chain(c.strip(), k.strip() or None)
                    by_host[host_part.strip().lower()] = hctx

                def pick(sock, server_name, _ctx):
                    if server_name:
                        hctx = by_host.get(server_name.lower())
                        if hctx is not None:
                            sock.context = hctx
                    return None  # unmatched names use the default chain

                ctx.sni_callback = pick
        except (OSError, _ssl.SSLError):
            log.exception("ssl listener context build failed; disabled")
            return None
        return ctx

    # ------------------------------------------------------------------
    # connection plumbing
    # ------------------------------------------------------------------

    def ensure_access_control(self):
        """REST-driven auth management attaches lazily: a node that
        booted with no auth gets a live chain on the first authenticator
        create (reference: authn/authz are runtime-configured)."""
        if self.access_control is None:
            self.access_control = attach_auth(
                self.broker,
                AuthChain(allow_anonymous=self.config.get(
                    "authn.allow_anonymous")),
                Authz(no_match=self.config.get("authz.no_match")),
            )
        return self.access_control

    def make_channel(self, conninfo: Optional[dict] = None) -> Channel:
        cfg = self.config
        return Channel(
            self.broker,
            self.cm,
            conninfo=conninfo,
            max_topic_alias=cfg.get("mqtt.max_topic_alias"),
            max_inflight=cfg.get("mqtt.max_inflight"),
            server_keepalive=(cfg.get("mqtt.server_keepalive") or None),
        )

    def _wants_intercept(self) -> bool:
        return (
            self.exhook is not None
            or self.cluster is not None
            or self.match_service is not None
            or (self.access_control is not None
                and self.access_control.needs_async())
        )

    def _register_on_connect(self, channel, conn) -> None:
        """Wrap handle_in so the clientid→connection registry fills the
        moment CONNECT lands (cheap and race-free on one loop)."""
        prev = channel.handle_in

        def handle_in_and_register(pkt):
            acts = prev(pkt)
            cid = channel.clientid
            if cid is not None and self.connections.get(cid) is not conn:
                if channel.state == "connected":
                    self.connections[cid] = conn
            return acts

        channel.handle_in = handle_in_and_register

    def make_protocol(self, info: ConnInfo):
        """Listener factory for the protocol-mode TCP datapath."""
        from .transport.proto_conn import MqttProtocol

        channel = self.make_channel(conninfo={"listener": info.listener})
        proto = MqttProtocol(
            channel,
            conninfo=info,
            max_packet_size=self.config.get("mqtt.max_packet_size"),
            limiter=self.limiter,
            on_closed=self._proto_closed,
            intercept=self._intercept if self._wants_intercept() else None,
            metrics=self.observed.metrics,
            # the batched-delivery stack is one opt-in: fanout pipeline
            # + ack-burst batching + write coalescing ride the same
            # flag, so the default datapath stays per-packet identical
            coalesce=bool(self.config.get("broker.fanout.enable")),
            wheel=self.timer_wheel,
        )
        if self._conn_hists is not None:
            (proto._h_parse, proto._h_queue, proto._h_intercept,
             proto._h_handle, proto._h_ack) = self._conn_hists
        channel.conn = proto
        self._register_on_connect(channel, proto)
        self._all_conns.add(proto)
        return proto

    def make_shard_protocol(self, shard):
        """Accept-time factory for a SHARD-owned connection: runs on
        the shard's loop, so everything it builds is shard-affine —
        the ShardChannel marshals broker-touching packets back here
        (transport/shards.py has the full thread-safety contract)."""
        from .transport.proto_conn import MqttProtocol  # noqa: F401
        from .transport.shards import ShardChannel, _ShardProtocol

        pool = self.shard_pool
        cfg = self.config
        info = ConnInfo(listener="tcp:default")
        channel = ShardChannel(
            pool, shard, self.broker, self.cm,
            conninfo={"listener": info.listener},
            max_topic_alias=cfg.get("mqtt.max_topic_alias"),
            max_inflight=cfg.get("mqtt.max_inflight"),
            server_keepalive=(cfg.get("mqtt.server_keepalive") or None),
        )
        proto = _ShardProtocol(
            channel,
            conninfo=info,
            max_packet_size=cfg.get("mqtt.max_packet_size"),
            limiter=shard.limiter,
            on_closed=pool.conn_closed,
            intercept=None,
            metrics=self.observed.metrics,
            coalesce=True,
            wheel=shard.wheel,
        )
        proto.shard = shard
        if shard.hists is not None:
            # the shard's OWN ingest_parse histogram: written only by
            # this shard's loop thread, merged at read time
            proto._h_parse = shard.hists.hist("obs.stage.ingest_parse")
        channel.conn = proto
        self._all_conns.add(proto)
        return proto

    def _proto_closed(self, proto) -> None:
        self._all_conns.discard(proto)
        self._conn_closed(proto)

    async def handle_stream(self, stream: Any, info: ConnInfo) -> None:
        """Listener entry: run one client connection to completion."""
        channel = self.make_channel(
            conninfo={"peername": stream.peername(), "listener": info.listener}
        )
        conn = Connection(
            stream,
            channel,
            conninfo=info,
            max_packet_size=self.config.get("mqtt.max_packet_size"),
            limiter=self.limiter,
            on_closed=self._conn_closed,
            # stream-path parity: the one batched-stack opt-in also
            # turns on ack-run ingest here (ws/quic/tcp-stream riders)
            coalesce=bool(self.config.get("broker.fanout.enable")),
            wheel=self.timer_wheel,
        )
        channel.conn = conn  # takeover routing (connection.py)
        self._register_on_connect(channel, conn)
        if self._wants_intercept():
            conn.intercept = self._intercept
        self._all_conns.add(conn)
        try:
            await conn.run()
        finally:
            self._all_conns.discard(conn)
            self.limiter.drop_conn(str(id(conn)))

    def _conn_closed(self, conn: Connection) -> None:
        cid = conn.channel.clientid
        if cid is not None and self.connections.get(cid) is conn:
            del self.connections[cid]

    def _on_deliver(self, clientid: str, pubs: List[Any]) -> None:
        conn = self.connections.get(clientid)
        if conn is None:
            self.broker.outbox_put(clientid, pubs)
            return
        shard = getattr(conn, "shard", None)
        if shard is not None:
            # reverse delivery path: serialize + write on the OWNING
            # shard loop (batched: one wakeup per drained burst)
            shard.post_deliver(conn, pubs)
        else:
            conn.deliver(pubs)

    def _kick_conn(self, conn, reason: str) -> None:
        """Kick that respects loop affinity: a shard-owned connection
        must be closed on its own loop."""
        shard = getattr(conn, "shard", None)
        if shard is None:
            conn.kick(reason)
        elif shard.alive():
            shard.post(lambda: conn.kick(reason))
        # dead shard: its cleanup already closed the socket

    def kick_client(self, clientid: str) -> bool:
        """Management 'kick out client' (emqx_mgmt:kickout_client).
        Also evicts an offline durable session (no live channel)."""
        had_session = clientid in self.broker.sessions
        chan = self.cm.kick(clientid)  # discards the broker session too
        conn = self.connections.pop(clientid, None)
        if conn is not None:
            self._kick_conn(conn, "kicked by management")
        self._disconnected_at.pop(clientid, None)
        return chan is not None or conn is not None or had_session

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def _intercept(self, channel, pkt):
        """Composite async pre-handle_in stage: cluster session migration
        first (a takeover must land before CONNECT resumes the session),
        then the TPU match prefetch (micro-batches concurrent publishes
        into one kernel call; Broker.publish consumes the hint), then the
        exhook advisory round trips."""
        from .mqtt import packet as P

        if (
            self.cluster is not None
            and pkt.type == P.CONNECT
            and channel.state == "idle"
        ):
            try:
                await self.cluster.prepare_connect(pkt)
            except Exception:
                log.exception("cluster takeover stage failed")
        if self.match_service is not None and pkt.type == P.PUBLISH \
                and self.broker.fanout is None:
            # with the fanout pipeline active the per-publish prefetch is
            # redundant: the pipeline batch-prefetches every topic in a
            # batch through ONE prefetch_many call at drain time
            try:
                await self.match_service.prefetch(pkt.topic, qos=pkt.qos)
            except Exception:
                log.exception("match prefetch failed (host path serves)")
        ac = self.access_control
        if ac is not None and ac.needs_async():
            # resolve network auth backends OFF the sync hook fold: the
            # verdicts park in the backends and the fold consumes them
            try:
                if pkt.type == P.CONNECT:
                    # enhanced-auth CONNECTs never run the authn chain —
                    # pre-resolving would query backends for nothing
                    if not (pkt.proto_ver == 5 and pkt.properties.get(
                            "Authentication-Method")):
                        await ac.preauthenticate(channel, pkt)
                elif pkt.type == P.PUBLISH:
                    # MQTT5 topic-alias publishes carry an empty topic;
                    # resolve through the channel's alias map so the
                    # prefetch covers the topic the sync fold authorizes
                    # (publish rewrite runs LATER, in broker.publish —
                    # the channel authorizes the original topic)
                    topic = channel.peek_topic(pkt)
                    if topic:
                        await ac.preauthorize(
                            channel.clientid, "publish", topic, pkt.qos)
                elif pkt.type == P.SUBSCRIBE:
                    # the subscribe rewrite hook (client.subscribe, prio
                    # 50) mutates the filters BEFORE the channel's
                    # authorize fold — prefetch the rewritten form
                    for flt, opts in pkt.topic_filters:
                        flt = self.rewrite.rewrite(
                            flt, "sub", channel.clientid,
                            self.broker.usernames.get(channel.clientid))
                        await ac.preauthorize(
                            channel.clientid, "subscribe", flt,
                            opts.get("qos", 0))
            except Exception:
                log.exception("async auth pre-resolution failed")
        if self.exhook is not None:
            return await self.exhook.intercept(channel, pkt)
        return None

    async def start(self) -> None:
        await self._start_match_service()
        await self._start_fanout()
        await self._start_cluster()
        await self._start_exhook()
        await self._start_mgmt()
        await self._start_gateways()
        if self.config.get("statsd.enable"):
            from .observe.statsd import StatsdPusher

            self.statsd = StatsdPusher(
                self.observed,
                server=self.config.get("statsd.server"),
                interval=self.config.get("statsd.flush_interval"),
                supervisor=self.supervisor,
                hist_source=self.hist_percentiles,
            )
            await self.statsd.start()
        if self.config.get("telemetry.enable"):
            from .observe.telemetry import Telemetry

            self.telemetry = Telemetry(
                self, url=self.config.get("telemetry.url"),
                interval=self.config.get("telemetry.interval"),
                supervisor=self.supervisor,
            )
            await self.telemetry.start()
        self._start_ocsp()
        await self._start_quic()
        self._maybe_shard()
        await self.listeners.start_all()
        self._running = True
        heap.keep_account(
            self.observed.metrics,
            self.hists.hist("obs.stage.gc_pause")
            if self.hists is not None else None)
        self._jobs.append(self.supervisor.start_child(
            "node.housekeeping", self._housekeeping))
        if self.lag_probe is not None:
            self._jobs.append(self.supervisor.start_child(
                "olp.lag_probe", self.lag_probe.run))
        if self.admission is not None:
            # the vectorized anomaly scorer: a crash/kill/injected
            # fault fails open (decisions clear, admission_degraded
            # alarm) and the supervisor restarts it
            self._jobs.append(self.supervisor.start_child(
                "admission.score", self.admission.run))

    def _maybe_shard(self) -> None:
        """Attach the connection-plane shard pool to the default TCP
        listener when configured and compatible (plain TCP fast path,
        batched stack on, no async advisory stage — see
        transport/shards.py for the exact contract)."""
        cfg = self.config
        n = int(cfg.get("broker.conn.shards") or 0)
        if n <= 0:
            return
        if not cfg.get("broker.fanout.enable"):
            log.warning("broker.conn.shards needs broker.fanout.enable; "
                        "sharding disabled")
            return
        if self._wants_intercept():
            log.warning("broker.conn.shards is incompatible with the "
                        "async advisory stage (exhook/cluster/tpu/async "
                        "auth); sharding disabled")
            return
        lst = self.listeners.get("tcp:default")
        if lst is None or lst.proto_factory is None \
                or lst.ssl_context is not None:
            log.warning("broker.conn.shards needs the plain-TCP "
                        "fast_path listener; sharding disabled")
            return
        from .transport.shards import ShardPool

        self.shard_pool = ShardPool(self, n)
        lst.shard_pool = self.shard_pool

    async def _start_quic(self) -> None:
        """MQTT-over-QUIC listener (quicer analog): the in-repo
        RFC 9000/9001 stack feeding stream 0 into handle_stream."""
        cfg = self.config
        if not cfg.get("listeners.quic.default.enable"):
            return
        cert = (cfg.get("listeners.quic.default.certfile")
                or cfg.get("listeners.ssl.default.certfile") or "").strip()
        key = (cfg.get("listeners.quic.default.keyfile")
               or cfg.get("listeners.ssl.default.keyfile") or "").strip()
        if not cert or not key:
            log.warning("quic listener enabled without a cert pair")
            return
        try:
            # cert reads off-loop: a slow/network filesystem must not
            # stall connections already being served (staticcheck:
            # no-blocking-in-async)
            from pathlib import Path
            cert_pem = await asyncio.to_thread(Path(cert).read_bytes)
            key_pem = await asyncio.to_thread(Path(key).read_bytes)
            from .transport.connection import ConnInfo
            from .transport.quic import QuicEndpoint

            bind = cfg.get("listeners.quic.default.bind")
            host, _, port = bind.rpartition(":")
            loop = asyncio.get_running_loop()

            class _Proto(asyncio.DatagramProtocol):
                def __init__(p) -> None:  # noqa: N805
                    pass

                def connection_made(p, transport) -> None:  # noqa: N805
                    self._quic_transport = transport

                def datagram_received(p, data, addr) -> None:  # noqa: N805
                    if self.quic is not None:
                        self.quic.datagram_received(data, addr)

            self._quic_transport, _ = await loop.create_datagram_endpoint(
                _Proto, local_addr=(host or "0.0.0.0", int(port)))
            self.quic_port = \
                self._quic_transport.get_extra_info("sockname")[1]
            try:
                # DF on outgoing datagrams: DPLPMTUD probes must test
                # the path, not be silently IP-fragmented en route
                import socket as _socket
                sock = self._quic_transport.get_extra_info("socket")
                if sock.family == _socket.AF_INET6:
                    sock.setsockopt(_socket.IPPROTO_IPV6,
                                    _socket.IPV6_MTU_DISCOVER,
                                    _socket.IPV6_PMTUDISC_DO)
                else:
                    sock.setsockopt(_socket.IPPROTO_IP,
                                    _socket.IP_MTU_DISCOVER,
                                    _socket.IP_PMTUDISC_DO)
            except (OSError, AttributeError):
                pass                    # non-Linux / wrapped transport

            async def on_connection(stream, info):
                await self.handle_stream(stream, ConnInfo(
                    peername=info.get("peername"),
                    listener="quic:default",
                ))

            self.quic = QuicEndpoint(
                self._quic_transport, cert_pem, key_pem, on_connection,
                max_connections=int(cfg.get(
                    "listeners.quic.default.max_connections")),
                supervisor=self.supervisor)
            log.info("quic listener on udp %s:%d", host, self.quic_port)
        except Exception:
            log.exception("quic listener failed to start")

    def _start_ocsp(self) -> None:
        """OCSP stapling cache for the TLS listener (emqx_ocsp_cache
        analog); the staple hand-off itself is gated on runtime ssl
        support — the cache keeps a fresh validated response either
        way (`node.ocsp_cache.info()` on the health surface)."""
        cfg = self.config
        if not cfg.get("listeners.ssl.default.ocsp.enable") \
                or not cfg.get("listeners.ssl.default.enable"):
            return  # no TLS listener ⇒ nothing to staple for
        cert = (cfg.get("listeners.ssl.default.certfile") or "").strip()
        issuer = (cfg.get("listeners.ssl.default.cacertfile") or "").strip()
        if not cert or not issuer:
            log.warning("ocsp enabled but certfile/cacertfile missing")
            return
        try:
            from .transport.ocsp import OcspCache

            with open(cert, "rb") as f:
                cert_pem = f.read()
            with open(issuer, "rb") as f:
                issuer_pem = f.read()
            self.ocsp_cache = OcspCache(
                cert_pem, issuer_pem,
                responder_url=(cfg.get(
                    "listeners.ssl.default.ocsp.responder_url") or None),
                refresh_interval_s=cfg.get(
                    "listeners.ssl.default.ocsp.refresh_interval"),
                refresh_http_timeout_s=cfg.get(
                    "listeners.ssl.default.ocsp.refresh_http_timeout"),
                supervisor=self.supervisor,
            )
            self.ocsp_cache.start()
        except Exception:
            log.exception("ocsp cache failed to start")

    async def _start_gateways(self) -> None:
        from .gateway import GatewayManager

        self.gateways = GatewayManager(self)
        for name in ("stomp", "mqttsn", "coap", "exproto", "lwm2m"):
            if not self.config.get(f"gateway.{name}.enable"):
                continue
            conf = {"bind": self.config.get(f"gateway.{name}.bind")}
            if name == "mqttsn":
                conf["gateway_id"] = self.config.get(
                    "gateway.mqttsn.gateway_id")
            elif name == "exproto":
                conf["handler"] = self.config.get("gateway.exproto.handler")
                conf["adapter_listen"] = self.config.get(
                    "gateway.exproto.adapter_listen")
            if name in ("coap", "lwm2m"):
                psk_raw = self.config.get(f"gateway.{name}.dtls.psk")
                psk = {}
                for p in psk_raw.split(","):
                    p = p.strip()
                    if ":" not in p:
                        continue
                    ident, hexkey = p.split(":", 1)
                    try:
                        psk[ident.strip()] = bytes.fromhex(hexkey.strip())
                    except ValueError:
                        # one bad entry disables one identity, not the
                        # whole gateway
                        log.warning("gateway.%s.dtls.psk: bad hex key for "
                                    "identity %r; entry skipped",
                                    name, ident.strip())
                conf["dtls"] = {
                    "enable": self.config.get(
                        f"gateway.{name}.dtls.enable"),
                    "psk": psk,
                }
            try:
                await self.gateways.load(name, conf)
            except Exception:
                log.exception("gateway %s failed to start", name)

    async def _start_match_service(self) -> None:
        if not self.config.get("tpu.enable"):
            return
        from .broker.match_service import MatchService

        cfg = self.config
        if cfg.get("match.segments.xla_cache"):
            enable_xla_cache()
        seg_dir = ""
        if cfg.get("match.segments.enable"):
            seg_dir = cfg.get("match.segments.dir") or os.path.join(
                cfg.get("node.data_dir") or "data", "segments")
        try:
            self.match_service = MatchService(
                self.broker,
                metrics=self.observed.metrics,
                depth=min(cfg.get("tpu.max_levels"), 16),
                batch_window_s=cfg.get("tpu.batch_deadline"),
                max_batch=cfg.get("tpu.batch_size"),
                debounce_s=cfg.get("tpu.mirror_refresh_interval"),
                active_slots=cfg.get("tpu.active_slots"),
                max_matches=cfg.get("tpu.max_matches"),
                max_stale_deltas=cfg.get("tpu.max_stale_deltas"),
                bypass_rate=cfg.get("tpu.bypass_rate"),
                prefetch_timeout_s=cfg.get("tpu.prefetch_timeout"),
                table=cfg.get("tpu.table"),
                short_depth=cfg.get("tpu.short_depth"),
                split_min=cfg.get("tpu.split_min"),
                deadline=cfg.get("match.deadline.enable"),
                deadline_s=cfg.get("match.deadline_ms") / 1e3,
                pipeline=cfg.get("match.pipeline.enable"),
                pipeline_depth=cfg.get("match.pipeline.depth"),
                breaker_threshold=cfg.get("match.breaker.threshold"),
                breaker_probe_interval_s=cfg.get(
                    "match.breaker.probe_interval"),
                alarms=self.observed.alarms,
                olp=self.olp,
                segments=cfg.get("match.segments.enable"),
                segments_dir=seg_dir,
                compact_interval_s=cfg.get(
                    "match.segments.compact_interval"),
                compact_min_mutations=cfg.get(
                    "match.segments.compact_min_mutations"),
                dirty_threshold=cfg.get("match.segments.dirty_threshold"),
                prewarm=cfg.get("match.segments.prewarm"),
                backend=cfg.get("match.backend"),
                autotune=cfg.get("match.autotune.enable"),
                autotune_reps=cfg.get("match.autotune.reps"),
                multichip=cfg.get("match.multichip.enable"),
                multichip_tp=cfg.get("match.multichip.tp"),
                multichip_native=cfg.get("match.multichip.native"),
                multichip_ep=cfg.get("match.multichip.ep.enable"),
                multichip_ep_slack=cfg.get(
                    "match.multichip.ep.capacity_slack"),
                multichip_ep_micro=cfg.get(
                    "match.multichip.ep.micro_matches"),
                multichip_ep_compact=cfg.get(
                    "match.multichip.ep.compact"),
                multichip_degraded=cfg.get(
                    "match.multichip.degraded.enable"),
                multichip_degraded_threshold=cfg.get(
                    "match.multichip.degraded.fail_threshold"),
                multichip_ep_overflow_warn=cfg.get(
                    "match.multichip.ep.overflow_warn"),
                multichip_ep_autotune=cfg.get(
                    "match.multichip.ep.autotune.enable"),
                multichip_ep_grow_threshold=cfg.get(
                    "match.multichip.ep.autotune.grow_threshold"),
                multichip_ep_shrink_threshold=cfg.get(
                    "match.multichip.ep.autotune.shrink_threshold"),
                multichip_ep_max_cap_class=cfg.get(
                    "match.multichip.ep.autotune.max_cap_class"),
                multichip_balance_budget=cfg.get(
                    "match.multichip.ep.autotune.max_moved_roots"),
                hists=self.hists,
                flightrec=self.flightrec,
            )
            self.match_service.supervisor = self.supervisor
            await asyncio.wait_for(
                self.match_service.start(),
                timeout=cfg.get("tpu.start_timeout"),
            )
            self.broker.device_match = self.match_service.hint_routes
            self.rule_engine.attach_match_service(self.match_service)
        except (Exception, asyncio.TimeoutError):
            log.exception("TPU match service unavailable; host trie serves")
            self.match_service = None

    async def _start_fanout(self) -> None:
        if not self.config.get("broker.fanout.enable"):
            return
        from .broker.fanout import FanoutPipeline

        cfg = self.config
        self.fanout_pipeline = FanoutPipeline(
            self.broker,
            metrics=self.observed.metrics,
            match_service=self.match_service,
            max_batch=cfg.get("broker.fanout.max_batch"),
            min_batch=cfg.get("broker.fanout.min_batch"),
            window_s=cfg.get("broker.fanout.window"),
            adapt_window_s=cfg.get("broker.fanout.adapt_window"),
            bypass_rate=cfg.get("broker.fanout.bypass_rate"),
            queue_cap=cfg.get("broker.fanout.queue_cap"),
            shape_routes=cfg.get("broker.fanout.shape_routes"),
            shape_probe_s=cfg.get("broker.fanout.shape_probe"),
            supervisor=self.supervisor,
            olp=self.olp,
            hists=self.hists,
            e2e_per_leg_sample=cfg.get("obs.hist.e2e_per_leg_sample"),
            flightrec=self.flightrec,
        )
        await self.fanout_pipeline.start()
        self.broker.fanout = self.fanout_pipeline
        self.observed.stats.provide(
            "broker.fanout.depth", self.fanout_pipeline.depth)

    async def _start_mgmt(self) -> None:
        if not self.config.get("dashboard.enable"):
            return
        from .mgmt import HttpServer, MgmtApi, basic_auth_checker
        from .mgmt.dashboard import DashboardUsers

        data_dir = (self.config.get("node.data_dir") or "").strip()
        self.dashboard_users = DashboardUsers(
            os.path.join(data_dir, "dashboard_users.json")
            if data_dir else None
        )

        bind = self.config.get("dashboard.listen")
        host, _, port = bind.rpartition(":")
        auth = None
        if self.config.get("dashboard.auth") or self.config.get(
            "api_key.enable"
        ):
            basic = (
                basic_auth_checker(
                    self.config.get("api_key.key"),
                    self.config.get("api_key.secret"),
                )
                if self.config.get("api_key.enable") else None
            )
            dash = self.dashboard_users
            # dashboard.auth=false + api_key.enable=true means the
            # operator chose api-key-ONLY auth: login tokens must not
            # reopen the write surface
            bearer_ok = bool(self.config.get("dashboard.auth"))

            def auth(req):
                # dashboard bearer token (role gates writes: viewer is
                # read-only, except self-service logout / own-password
                # change) OR api-key basic auth when enabled
                hdr = req.headers.get("authorization", "")
                if hdr.startswith("Bearer ") and bearer_ok:
                    tok = hdr.removeprefix("Bearer ").strip()
                    write = req.method not in ("GET", "HEAD")
                    if req.path == "/api/v5/logout":
                        write = False
                    elif (req.path.startswith("/api/v5/users/")
                          and req.path.endswith("/change_pwd")):
                        who = req.path.removeprefix(
                            "/api/v5/users/").removesuffix("/change_pwd")
                        if dash.token_user(tok) == who:
                            write = False
                    return dash.check_token(tok, write=write)
                return basic(req) if basic is not None else False
        elif (host or "0.0.0.0") not in ("127.0.0.1", "localhost", "::1"):
            log.warning(
                "management API on %s without auth: any network peer can "
                "kick clients, publish, and mutate config", bind
            )
        self.mgmt_server = HttpServer(
            host or "0.0.0.0", int(port), auth=auth,
            auth_exempt=("/api/v5/status", "/api/v5/login",
                         "/", "/dashboard"),
        )
        self.mgmt = MgmtApi(self, self.mgmt_server)
        await self.mgmt_server.start()

    async def _start_cluster(self) -> None:
        if not self.config.get("cluster.enable"):
            return
        from .cluster import Cluster

        self.cluster = Cluster(
            self,
            listen=self.config.get("cluster.listen"),
            seeds=self.config.get("cluster.seeds"),
            cluster_name=self.config.get("cluster.name"),
        )
        self.cluster.HEARTBEAT_INTERVAL = self.config.get(
            "cluster.heartbeat_interval"
        )
        self.cluster.NODE_TIMEOUT = self.config.get("cluster.node_timeout")
        await self.cluster.start()

    async def _start_exhook(self) -> None:
        spec = (self.config.get("exhook.servers") or "").strip()
        if not spec:
            return
        from .exhook import ExHookManager, ServerSpec

        servers = []
        for part in spec.split(","):
            name, _, url = part.strip().partition("=")
            if not url:
                log.warning(
                    "exhook.servers entry %r ignored (expected name=host:port)",
                    part.strip(),
                )
                continue
            servers.append(
                ServerSpec(
                    name=name, url=url,
                    timeout=self.config.get("exhook.request_timeout"),
                    failure_action=self.config.get("exhook.failure_action"),
                )
            )
        if servers:
            self.exhook = ExHookManager(self, servers)
            await self.exhook.start()

    async def stop(self) -> None:
        self._running = False
        heap.drop_account(self.observed.metrics)
        self.plugins.stop_all()
        if self.statsd is not None:
            await self.statsd.stop()
            self.statsd = None
        if self.telemetry is not None:
            await self.telemetry.stop()
            self.telemetry = None
        if getattr(self, "gateways", None) is not None:
            await self.gateways.stop_all()
        if self.ocsp_cache is not None:
            self.ocsp_cache.stop()
            self.ocsp_cache = None
        if self.quic is not None:
            self.quic.close()
            self.quic = None
        await self.bridges.stop_all()
        if self.fanout_pipeline is not None:
            # detach first so the drain-on-stop republishes (and any
            # in-flight channel offers) take the sync path
            self.broker.fanout = None
            await self.fanout_pipeline.stop()
            self.fanout_pipeline = None
        if self.match_service is not None:
            await self.match_service.stop()
            self.broker.device_match = None
            self.match_service = None
        if self.exhook is not None:
            await self.exhook.stop()
            self.exhook = None
        if self.cluster is not None:
            await self.cluster.stop()
            self.cluster = None
        if self.mgmt_server is not None:
            await self.mgmt_server.stop()
            self.mgmt_server = None
            self.mgmt = None
        # housekeeping must be gone BEFORE persistence.close(): a
        # sync_async still running _write in a worker thread would race
        # close()'s final sync/compact on the same WAL handle
        for job in self._jobs:
            job.cancel()
        if self._jobs:
            await asyncio.gather(*self._jobs, return_exceptions=True)
        self._jobs.clear()
        # sweep the supervision tree: any child not already stopped by
        # its subsystem's stop() goes down here, reverse boot order
        await self.supervisor.stop()
        if self.persistence is not None:
            self.persistence.close()
        # kick live connections BEFORE awaiting listener close: 3.12's
        # Server.wait_closed() blocks until every connection handler
        # returns, so the order matters.  _all_conns covers sockets that
        # never completed CONNECT (absent from self.connections).
        for conn in list(self._all_conns):
            self._kick_conn(conn, "node shutdown")
        # give connections a beat to flush their goodbyes
        await asyncio.sleep(0)
        await self.listeners.stop_all()
        if self.timer_wheel is not None:
            self.timer_wheel.close()

    async def _housekeeping(self) -> None:
        """Periodic jobs: delayed-publish firing, retained expiry, session
        expiry, banned-table cleanup ($SYS heartbeat lives in observe)."""
        interval = 1.0
        while self._running:
            await asyncio.sleep(interval)
            try:
                heap.report(self.observed.metrics)
                if self.timer_wheel is not None:
                    # aggregate wheel-resident timer gauge: main-loop
                    # wheel + every shard wheel (racy cross-thread int
                    # reads — a gauge, not an invariant)
                    conns = len(self.timer_wheel)
                    if self.shard_pool is not None:
                        conns += self.shard_pool.wheel_conns()
                    self.observed.metrics.set(
                        "broker.timer.wheel_conns", conns)
                if self.delayed is not None:
                    self.delayed.tick()
                if self.retainer is not None:
                    self.retainer.clean_expired()
                self.banned.clean_expired()
                # per-client keyed-state growth bounds (churn audit):
                # flapping windows and idle limiter bucket pairs are
                # swept here; admission feature rows evict themselves
                # inside score_tick (idle_expiry)
                now_mono = time.monotonic()
                if now_mono - self._last_idle_sweep >= 60.0:
                    self._last_idle_sweep = now_mono
                    self.flapping.sweep()
                    self.limiter.sweep_idle(600.0)
                self._expire_sessions()
                if self.quic is not None:
                    self.quic.sweep()
                if self.persistence is not None:
                    sync_iv = self.config.get(
                        "durable_storage.sync_interval"
                    )
                    if time.time() - self.persistence.last_sync >= sync_iv:
                        await self.persistence.sync_async()
            except Exception:
                log.exception("housekeeping job failed")

    def _expire_sessions(self) -> None:
        """MQTT session-expiry: drop sessions whose client stayed away past
        Session-Expiry-Interval (emqx_cm session GC)."""
        now = time.time()
        for cid, t in list(self._disconnected_at.items()):
            sess = self.broker.sessions.get(cid)
            if sess is None or self.cm.lookup_channel(cid) is not None:
                del self._disconnected_at[cid]
                continue
            if now - t >= sess.expiry_interval:
                self.broker.close_session(cid, discard=True)
                del self._disconnected_at[cid]

    # ------------------------------------------------------------------

    def quic_listener_info(self) -> list:
        """QUIC listener row(s) — ONE shape shared by node.info() and
        GET /api/v5/listeners (drift between the two was a review
        finding)."""
        if self.quic is None:
            return []
        conns = self.quic.live_conns()
        return [{
            "id": "quic:default", "type": "quic",
            "bind": f"udp:{self.quic_port}", "running": True,
            "current_connections": len(self.quic.streams),
            "handshakes": self.quic.handshakes,
            "dropped_initials": self.quic.dropped_initials,
            "retransmits": self.quic.retransmits,
            # recovery/path state rolled up over live connections: the
            # operator-facing view of RFC 9002 loss detection and
            # DPLPMTUD (fast_retransmits = ack-evidence losses healed
            # without a timer; mtu_validated_max = largest datagram
            # budget any live path proved)
            "fast_retransmits": sum(c.fast_retransmits for c in conns),
            "mtu_probes_sent": sum(c.mtu_probes_sent for c in conns),
            "mtu_validated_max": max(
                (c.mtu_validated for c in conns), default=1252),
        }]

    def info(self) -> dict:
        from . import __version__

        return {
            "node": self.node_name,
            "version": __version__,
            "uptime": time.time() - self.started_at,
            "connections": len(self.connections),
            "listeners": ([l.info() for l in self.listeners.all()]
                          + self.quic_listener_info()),
            "gateways": (self.gateways.list()
                         if self.gateways is not None else []),
            "bridges": len(self.bridges.list()),
            "rules": len(self.rule_engine.rules),
            "plugins": self.plugins.list(),
            "auth": {"authenticators": len(self._auth_confs),
                     "sources": len(self._authz_confs),
                     "attached": self.access_control is not None},
            "topic_metrics": len(self.topic_metrics.topics()),
            "cluster_peers": sorted(self.cluster.peers)
            if self.cluster is not None else [],
            "tpu_match": (self.match_service.info()
                          if self.match_service is not None else None),
            "fanout": (self.fanout_pipeline.info()
                       if self.fanout_pipeline is not None else None),
            "supervisor": self.supervisor.info(),
            "flightrec": self.flightrec.info(),
            "admission": (self.admission.info()
                          if self.admission is not None else None),
            "gc": heap.report(self.observed.metrics),
            **self.broker.stats(),
        }

    # -- stage-level latency observatory (observe/hist.py) -------------

    def hist_sets(self) -> List[Any]:
        """Every live plane's histogram set: the main set (also written
        by the match worker stages — one writer per histogram) plus one
        per shard loop.  Empty when ``obs.hist.enable`` is off."""
        if self.hists is None:
            return []
        sets = [self.hists]
        pool = self.shard_pool
        if pool is not None:
            sets.extend(s.hists for s in pool.shards
                        if s.hists is not None)
        return sets

    def hist_percentiles(self) -> Dict[str, Dict[str, float]]:
        """Merged cross-plane percentiles — the one latency definition
        every export surface ($SYS, REST/CLI, statsd, bench) reads."""
        from .observe.hist import HistSet

        return HistSet.percentiles(self.hist_sets())
