"""Typed configuration with text-file + ``key=val`` CLI override merging.

TPU-native rebuild of the reference's three config styles (SURVEY.md §5.6):
protobuf-text conf files merged with CLI overrides (reference
``learn/linear/base/arg_parser.h:13-64`` + ``proto/config.proto:6-110``) and the
``param=val`` SetParam chains of the rabit apps
(``learn/lbfgs-linear/linear.cc:236-241``). Here a single dataclass-backed
parser covers both: conf files hold one ``key = value`` (or ``key: value``)
per line, CLI args are ``key=value`` tokens, CLI merges over file (same
precedence as the reference).
"""

from __future__ import annotations

import dataclasses
import enum
import typing
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence


class Loss(enum.Enum):
    SQUARE = "square"
    LOGIT = "logit"
    HINGE = "hinge"
    SQUARE_HINGE = "square_hinge"


class Penalty(enum.Enum):
    L1 = "l1"
    L2 = "l2"


class Algo(enum.Enum):
    # (minibatch) online methods
    SGD = "sgd"
    ADAGRAD = "adagrad"
    FTRL = "ftrl"
    # batch methods
    LBFGS = "lbfgs"
    # delay tolerant, experimental
    DT_SGD = "dt_sgd"
    DT_ADAGRAD = "dt_adagrad"
    DT2_ADAGRAD = "dt2_adagrad"


@dataclass
class Config:
    """Mirror of the reference Config schema (``proto/config.proto:6-110``),
    extended with TPU-runtime knobs (mesh shape, bucket count, dtype)."""

    # --- data ---
    train_data: str = ""
    val_data: str = ""
    test_data: str = ""
    data_format: str = "libsvm"
    num_parts_per_file: int = 1
    # straggler re-execution threshold (workload_pool.h FLAGS analogue):
    # a part running straggler_factor x the mean completed-part duration
    # is re-issued. Multihost passes measure duration in lockstep ROUNDS
    # (deterministic across replicas); single-process in wall-clock.
    straggler_factor: float = 3.0
    # dense text fast path: binary-feature text formats (criteo/adfea)
    # stream as natively-assembled in-memory crec blocks through the
    # dense-apply device step instead of localize+pad in Python.
    # NOTE: this path folds keys with mix32 (the crec fold) while the
    # multi-process sparse path folds splitmix64, so a model saved from
    # a single-process text run cannot warm-start a multi-process run of
    # the same data (load_model hard-errors on the recorded key_fold);
    # set text_dense=false when a model must move between launch modes
    text_dense: bool = True
    text_block_rows: int = 16384

    # --- model ---
    model_in: str = ""
    model_out: str = ""
    pred_out: str = ""  # predictions for test_data (TEST workload output)

    loss: Loss = Loss.LOGIT
    penalty: Penalty = Penalty.L1
    lambda_: List[float] = field(default_factory=list)  # "lambda" in the reference

    # --- optimization ---
    algo: Algo = Algo.FTRL
    minibatch: int = 1000
    max_data_pass: int = 10
    disp_itv: float = 1.0

    # --- observability (obs/ subsystem; all off by default) ---
    # Chrome trace-event JSON destination: non-empty turns span tracing
    # on; the file loads in Perfetto (ui.perfetto.dev). Rank > 0 hosts
    # write <path>.r<rank>.json. See docs/observability.md.
    trace_path: str = ""
    # directory for per-host heartbeat JSON-lines + run-end Prometheus
    # dump; empty = no telemetry files. launch_mp --heartbeat-dir sets
    # the WORMHOLE_METRICS_EXPORT fallback for its workers.
    metrics_export: str = ""
    # min seconds between heartbeat records (obs/heartbeat.py rate limit)
    heartbeat_itv: float = 5.0
    # timeline sampler interval (obs/timeline.py): > 0 starts the
    # rolling-window daemon sampler; samples spill to
    # host<rank>.timeline.jsonl under metrics_export. 0 = off.
    metrics_sample_itv_s: float = 0.0
    # max timeline samples held in the in-memory ring; older samples
    # are evicted into the timeline/dropped_samples counter
    timeline_ring: int = 512
    # min seconds between periodic fsync+rename ring spills; the final
    # spill at finalize always happens. <= 0 = final spill only.
    timeline_spill_itv_s: float = 10.0
    # SLO objectives (obs/slo.py; each 0 = that objective undeclared):
    # rolling serve p99 ceiling in ms
    slo_serve_p99_ms: float = 0.0
    # max first-vs-last-quartile ex/s decay fraction over the window
    slo_exs_drift_frac: float = 0.0
    # ps/staleness ceiling (windows of delay)
    slo_ps_staleness: float = 0.0
    # max host-RSS growth in MB/min (the leak detector)
    slo_rss_mb_per_min: float = 0.0
    # rolling window (seconds) burn rates are computed over
    slo_window_s: float = 60.0
    # flight recorder (obs/flight.py): non-empty directory arms crash
    # bundles (flight_<reason>_<step>/) on failure edges. "" = off.
    flight_dir: str = ""
    # seconds of pre-failure timeline kept in a flight bundle
    flight_window_s: float = 30.0
    epsilon: float = 0.0   # early stop when a pass improves per-example
                           # objv by less than this fraction; 0 = off
    max_objv: float = 0.0  # 0 = unset; stop if objv >= max_objv

    lr_eta: float = 0.1
    lr_beta: float = 1.0
    lr_theta: float = 1.0

    # --- sync-cost reduction ---
    # The reference's ps-lite message filters (KEY_CACHING / COMPRESSING
    # / FIXING_FLOAT, OSDI'14 §5.1) live in parallel/filters.py, ported
    # from the key-vector wire format to pytree *collective sites*:
    # keys never transit our network (text-path batches fold keys on the
    # host feeding its own devices, crec paths fold them on device), so
    # KEY_CACHING caches each site's leaf metadata instead; COMPRESSING
    # and FIXING_FLOAT apply to the host-collective payloads on the DCN
    # path. `comm_filters` (off by default) turns them on; the older
    # `msg_compression` / `fixed_bytes` knobs are narrower per-call-site
    # switches that predate the chain (see docs/comm.md).
    # bounded staleness: max device steps in flight. Single-host process()
    # gates BEFORE dispatch (the reference parses the next minibatch while
    # steps fly, async_sgd.h:81), so 0 and 1 behave identically — device
    # steps on one chip serialize anyway; the multihost pass gates AFTER
    # dispatch, where max_delay=0 means fully synchronous global steps.
    max_delay: int = 0
    msg_compression: bool = False  # zlib-compress host-collective payloads
    fixed_bytes: int = 1
    tail_feature_freq: int = 0
    # communication filter chain (parallel/filters.py): comma set from
    # {key_caching, fixing_float, compressing}; "" = chain off, every
    # host collective runs the raw unfiltered transport.
    comm_filters: str = ""
    comm_quant_bits: int = 8          # FIXING_FLOAT code width, in [2, 16]
    comm_compress_min_bytes: int = 1024  # COMPRESSING skips smaller leaves
    # --- bounded-staleness async exchange (wormhole_tpu/ps) ---
    # staleness_tau routes the multihost training exchange through the
    # ExchangeEngine's background thread (docs/async_ps.md): the train
    # loop runs up to tau gradient windows ahead of the freshest
    # globally-applied delta before blocking. -1 = engine off (the
    # direct BSP collective path, the default); 0 = engine on but fully
    # synchronous — bit-identical to BSP, the parity oracle; >= 1
    # overlaps the DCN exchange with local compute, feeding the DT
    # handles the measured per-window delay.
    staleness_tau: int = -1
    # device steps folded into one exchanged delta window (>= 1)
    ps_window_steps: int = 1
    # engine queue bound; 0 = derive from staleness_tau (tau + 1)
    ps_queue_depth: int = 0
    # live-rejoin delta replay (ft/rejoin.py): each engine keeps the
    # last max(staleness_tau, 0) + rejoin_replay_windows reduced delta
    # windows so a relaunched rank can catch up from checkpoint +
    # replay instead of a stop-the-world relaunch. 0 = no replay log
    # (rejoin machinery fully off; wire bytes and tau=0 parity are
    # untouched).
    rejoin_replay_windows: int = 0
    # --- 2D hierarchical exchange (parallel/transport.py) ---
    # hier_hosts > 0 arranges the run as that many hosts, each running
    # its own (data, model) mesh over ICI, exchanging only host-level
    # bucket deltas cross-host through the filtered wire. The cross-host
    # leg rides staleness_tau unchanged: -1/0 = synchronous delta
    # exchange per window (tau=0 is the BSP parity oracle), >= 1 lets
    # each host run tau windows ahead through its ExchangeEngine.
    # 0 = hierarchy off (flat single-level exchange, the default).
    hier_hosts: int = 0
    # per-host mesh geometry for the hierarchy, same grammar as
    # mesh_shape (e.g. "data:2,model:2"); empty = each host puts all its
    # local devices on "data". Ignored unless hier_hosts > 0.
    hier_mesh_shape: str = ""
    # --- cross-host wire (parallel/socket_wire.py) ---
    # which transport carries the cross-host leg (hier/delta, fleet
    # snapshot fan-out, rejoin ctl): "process" = jax.distributed
    # collectives (the default; intra-host stays on ICI either way),
    # "socket" = the repo-owned TCP wire (real multi-process bytes,
    # needs wire_rendezvous), "sim" = in-process SimBus threads (the
    # deterministic oracle; world size 1 only).
    wire: str = "process"
    # shared rendezvous directory for wire=socket peer discovery (rank
    # adverts + rank-0 peer table, committed tmp+fsync+replace); falls
    # back to the WORMHOLE_WIRE_RENDEZVOUS env var when empty.
    wire_rendezvous: str = ""
    # per-peer bounded outbox depth, in frames: how far FilterChain
    # encode may run ahead of socket I/O before the sender backpressures
    wire_outbox_depth: int = 8

    # --- L-BFGS specifics (reference learn/solver/lbfgs.h SetParam surface) ---
    max_lbfgs_iter: int = 100
    lbfgs_memory: int = 10  # size_memory
    reg_L1: float = 0.0
    reg_L2: float = 0.0
    linesearch_c1: float = 1e-4
    linesearch_backoff: float = 0.5
    max_linesearch_iter: int = 30
    min_lbfgs_iter: int = 5

    # --- TPU runtime (new; no reference analogue) ---
    num_buckets: int = 1 << 20  # hashed parameter-bucket count (FLAGS_max_key analogue)
    max_nnz: int = 0            # 0 = derive from data; per-row padded nnz
    key_pad: int = 0            # static unique-key padding; REQUIRED (with
                                # max_nnz) for multi-host sync training,
                                # where batch shapes must match across hosts
    mesh_shape: str = ""        # e.g. "data:4,model:2"; empty = all devices on "data"
    # model-axis sharding shorthand: with mesh_shape empty, shard the
    # (num_buckets,) slot planes over a "model" axis of this size and
    # put the remaining devices on "data" (parallel/mesh.py
    # derive_mesh_shape). 0/1 = no model axis; ignored when mesh_shape
    # names axes explicitly.
    model_shards: int = 0
    # --- bigmodel hot/cold tiering (wormhole_tpu/bigmodel; see
    # docs/bigmodel.md). Consumed by PagedStore.from_config and the
    # bench bigmodel phase; 0 = whole table device-resident.
    hot_buckets: int = 0     # on-device hot working set, in buckets,
                             # backed by the full num_buckets cold table
                             # in host RAM
    page_prefetch: int = 8   # extra late-fill window slack (plans) on
                             # top of the pipeline lookahead bound —
                             # how much further a page-in may be staged
                             # ahead through the transfer ring
    page_chunk: int = 64     # padding quantum (rows) for paging
                             # gather/scatter index vectors; bounds the
                             # number of compiled paging programs
    cache_device: bool = False  # crec/crec2: keep streamed blocks resident in
                                # HBM and replay them on later data passes
                                # (dataset must fit device memory)
    param_dtype: str = "float32"  # slots-table storage dtype ("float32" or
                                  # "bfloat16"; bf16 halves table HBM at
                                  # the cost of accumulator precision)
    # staged ingest pipeline (data/pipeline.py DeviceFeed): localize+pad
    # (sparse path) or block read/assembly (crec/text paths) run on
    # pipeline_workers threads while a transfer thread keeps
    # pipeline_ring device-resident batches ahead of the compute loop.
    # 0 = the serial feed path (every stage inline on the consumer).
    pipeline_workers: int = 2
    pipeline_ring: int = 2
    # online tile encoding (data/crec.TileOnlineFeed): fold+tile-group
    # streaming blocks (crec v1 / dense-text) on the pipeline workers and
    # run the MXU tile step instead of gather/scatter or dense-apply.
    # "auto" engages on the TPU backend when the store has a tile step,
    # the run is single-process and the tilemm limits admit the geometry;
    # "on" forces it (errors when inadmissible — the parity-test mode);
    # "off" keeps the existing scatter/dense paths. crec2 files are
    # already tile-grouped and ignore this knob.
    tile_online: str = "auto"
    # tile train-step kernel (ops/tilemm.py): "fused" runs fwd margins,
    # loss dual, grad histogram (and the FTRL update in place on the
    # single-process path) as ONE two-phase pallas grid, so neither the
    # margin grid nor the (nb,) gradient round-trips HBM; "split" keeps
    # the two-call fwd/bwd oracle (the bit-parity reference and the
    # structural fallback for mesh shards — spill blocks fuse via a
    # pre-aggregated margin operand and deep stores via the in-kernel
    # MLP phase when the VMEM budget admits it); "auto" fuses on the
    # TPU backend when the geometry admits it.
    tile_step_kernel: str = "auto"
    # phase-shared one-hot cache inside the fused grid (ops/tilemm.py):
    # phase 1 stages the per-(group, tile) packed-word relayouts and
    # digit one-hot planes in VMEM scratch, phase 2 replays them into
    # the grad-histogram chains instead of rebuilding. "auto" admits the
    # cache when the plane bytes fit beside the kernel's working set
    # (resolve_step_kernel's VMEM budget model); "on" forces it past the
    # budget check (measurement mode — structural exclusions still
    # hold); "off" always rebuilds. The resolution is recorded as
    # onehot_cache=on|off:<why> in store.step_kernel.
    tile_onehot_cache: str = "auto"
    seed: int = 0
    checkpoint_dir: str = ""
    checkpoint_every: int = 1   # save a checkpoint every N data passes
    # online serving (wormhole_tpu/serve): admission-batching front-end
    # geometry + latency budget, snapshot hot-swap cadence, and offline
    # predict routing. See docs/serving.md.
    serve_batch: int = 256        # admission batch rows (device batch size)
    serve_max_nnz: int = 64       # per-request feature cap (positional trunc)
    serve_deadline_ms: float = 5.0  # flush when the oldest admitted request
                                    # has waited this long (latency budget)
    serve_poll_itv: float = 2.0   # snapshot poller interval, seconds
    serve_predict: bool = True    # route offline predict() TEST margins
                                  # through the pull-only serve forward
                                  # (eval_step stays the metrics oracle)
    # --- serve fleet (wormhole_tpu/serve/fleet.py): N replicas behind
    # the consistent-hash router, freshness via delta snapshot shipping
    # over the 'serve/snapshot' transport site. See docs/serving.md.
    serve_fleet_replicas: int = 1   # frontend replica count (1 = solo tier)
    serve_fleet_router: str = "spill"  # "hash" (pure consistent-hash) or
                                       # "spill" (+ least-loaded escape)
    serve_fleet_vnodes: int = 128   # ring virtual nodes per replica
    serve_fleet_spill_frac: float = 2.0  # spill when owner depth exceeds
                                         # this multiple of the fleet mean
    serve_fleet_full_every: int = 16  # every Nth snapshot frame ships full
                                      # (exact); rest are quantized deltas.
                                      # 1 = full-only, 0 = fulls on gap only
    # --- deadline-aware load shedding (frontend priority queue) ---
    serve_shed_enable: bool = True  # shed sheddable-class work when the
                                    # projected queue wait exceeds the
                                    # deadline (class 0 is never shed)
    serve_shed_engage: float = 0.8  # arm shedding once rolling p99 reaches
                                    # this fraction of the SLO ceiling
                                    # (engage before budget burn)
    serve_shed_storm: int = 64      # sheds within 5s that count as a storm
                                    # (one FlightRecorder dump each)
    # --- fault tolerance (wormhole_tpu/ft; all off by default) ---
    # collective watchdog: a survivor blocked in a host collective longer
    # than this many seconds exits with the distinguished PEER_LOST code
    # (117) instead of hanging on a dead peer. 0 = no watchdog thread.
    # See docs/fault_tolerance.md.
    comm_timeout_s: float = 0.0
    # supervised launch_mp (mirrored by --ft-dead-after): declare a rank
    # dead after this many seconds of heartbeat silence and trigger the
    # drain + relaunch cycle. 0 = unsupervised.
    ft_dead_after_s: float = 0.0
    # relaunch geometry after a dead rank: "fixed" re-runs at the same
    # world size, "shrink" drops to the survivors (floor 2), "rejoin"
    # keeps survivors running and respawns only the dead rank, which
    # catches up via checkpoint + delta replay (ft/rejoin.py)
    ft_elastic: str = "fixed"
    # --- chaos fault injection (ft/chaos.py; inert unless set, and only
    # ever fires on attempt 0 of a supervised run) ---
    chaos_kill_rank: int = -1     # SIGKILL this rank (-1 = off) ...
    chaos_kill_block: int = 0     # ... once it has produced this many blocks
    chaos_delay_rank: int = -1    # rank receiving the injected delays below
    chaos_collective_delay_s: float = 0.0  # sleep before each host collective
    chaos_heartbeat_delay_s: float = 0.0   # sleep inside each heartbeat write
    chaos_ckpt_errors: int = 0    # transient checkpoint-IO errors to inject
    # sleep inside the live-rejoin handshake before the rejoiner attaches
    # (stretches the replay gap the bounded log must absorb)
    chaos_rejoin_handshake_delay_s: float = 0.0
    # transient OSErrors injected into the rejoin-path latest_version
    # directory scans (torn read racing a concurrent save; retried once)
    chaos_rejoin_ckpt_transient: int = 0

    def merged(self, kvs: Sequence[str]) -> "Config":
        """Return a copy with ``key=value`` tokens merged over this config."""
        out = dataclasses.replace(self)
        apply_kvs(out, kvs)
        return out


def check_choice(name: str, value: str, choices: Sequence[str]) -> str:
    """Validate a string-enum config knob at construction time (shared
    by model configs whose dataclass fields are plain ``str`` — e.g.
    ``gbdt_hist_kernel`` — so a typo'd ``key=val`` CLI token fails fast
    instead of deep inside a training pass)."""
    if value not in choices:
        raise ValueError(
            f"{name} must be one of {tuple(choices)}, got {value!r}")
    return value


_ALIASES = {
    "lambda": "lambda_",
    "size_memory": "lbfgs_memory",
    "max_iter": "max_lbfgs_iter",
}


def _coerce(ftype: Any, raw: str) -> Any:
    """Coerce a raw string to the declared field type."""
    raw = raw.strip().strip("'\"")
    origin = typing.get_origin(ftype)
    if origin in (list, List, tuple):   # List[x], Tuple[x, ...]
        inner = typing.get_args(ftype)[0]
        items = [_coerce(inner, p)
                 for p in raw.replace(",", " ").split() if p]
        return tuple(items) if origin is tuple else items
    if origin is typing.Union:  # Optional[...]
        inner = [a for a in typing.get_args(ftype) if a is not type(None)]
        return _coerce(inner[0], raw)
    if isinstance(ftype, type) and issubclass(ftype, enum.Enum):
        key = raw.lower()
        for m in ftype:
            if m.value == key or m.name.lower() == key:
                return m
        raise ValueError(f"unknown {ftype.__name__} value: {raw!r}")
    if ftype is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if ftype is int:
        return int(float(raw))
    if ftype is float:
        return float(raw)
    return raw


def apply_kvs(cfg: Any, kvs: Sequence[str],
              aliases: Optional[dict] = None) -> None:
    """Merge ``key=value`` tokens into ANY dataclass instance (typed by its
    field annotations) — the ``param=val`` SetParam chain of the rabit apps
    (lbfgs-linear/linear.cc:236-241) for arbitrary app configs."""
    hints = typing.get_type_hints(type(cfg))
    alias = dict(_ALIASES if isinstance(cfg, Config) else {})
    alias.update(aliases or {})
    for tok in kvs:
        tok = tok.strip()
        if not tok or tok.startswith("#"):
            continue
        if "=" in tok:
            key, _, val = tok.partition("=")
        elif ":" in tok:
            key, _, val = tok.partition(":")
        else:
            raise ValueError(f"cannot parse config token {tok!r} (want key=val)")
        key = key.strip()
        key = alias.get(key, key)
        if not hasattr(cfg, key):
            raise ValueError(f"unknown config key {key!r}")
        setattr(cfg, key, _coerce(hints[key], val))


def _append_repeated(lines: List[str]) -> List[str]:
    """Collapse repeated keys (proto2 ``repeated`` semantics) into one list token.

    ``lambda = 1`` + ``lambda = 0.1`` becomes ``lambda = 1 0.1``, matching the
    reference's repeated-field conf style (``guide/criteo_s3.conf``)."""
    hints = typing.get_type_hints(Config)
    merged: dict = {}
    order: List[str] = []
    for ln in lines:
        key = _ALIASES.get(ln.partition("=")[0].partition(":")[0].strip(),
                           ln.partition("=")[0].partition(":")[0].strip())
        is_rep = key in hints and typing.get_origin(hints[key]) in (list, List)
        val = ln.partition("=")[2] if "=" in ln else ln.partition(":")[2]
        if key not in merged:
            merged[key] = []
            order.append(key)
        if is_rep:
            merged[key].append(val.strip())
        else:
            merged[key] = [val.strip()]
    return [f"{k}={' '.join(merged[k])}" for k in order]


def load_config(path: Optional[str] = None,
                argv: Sequence[str] = (),
                base: Optional[Config] = None) -> Config:
    """Load a conf file then merge ``key=value`` CLI tokens over it.

    Matches reference precedence: file first, CLI overrides
    (``arg_parser.h:36-45``)."""
    cfg = dataclasses.replace(base) if base is not None else Config()
    if path:
        from wormhole_tpu.data.stream import open_stream
        with open_stream(path, "r") as f:
            text = f.read()
        if isinstance(text, bytes):
            text = text.decode("utf-8")
        lines = [ln.strip() for ln in text.splitlines()
                 if ln.strip() and not ln.strip().startswith("#")]
        apply_kvs(cfg, _append_repeated(lines))
    apply_kvs(cfg, list(argv))
    return cfg
