"""AUTOGENERATED by `python scripts/rltcheck.py --write-knobs` — do
not edit by hand. One entry per ``RLT_*`` env knob the code reads,
writes, or mentions; the rltcheck gate fails when this file is
stale or when a knob is neither documented nor allowlisted."""


KNOBS = {
    'RLT_ACTOR_PROCESS': {
        "modules": ['runtime.actor_boot', 'runtime.compile_cache', 'runtime.zygote'],
        "defaults": ["'1'"],
        "read": False, "set": True,
    },
    'RLT_ACTOR_READY': {
        "modules": ['runtime.api', 'runtime.zygote'],
        "read": False, "set": False,
    },
    'RLT_BIND_HOST': {
        "modules": ['runtime.actor', 'runtime.node', 'runtime.queue'],
        "defaults": ["'0.0.0.0'"],
        "read": True, "set": True,
    },
    'RLT_CHIPS_PER_HOST': {
        "modules": ['launchers.ray_launcher'],
        "read": True, "set": False,
    },
    'RLT_CKPT_EVERY_N_STEPS': {
        "modules": ['callbacks.orbax_checkpoint'],
        "read": True, "set": False,
    },
    'RLT_COMPILE_CACHE': {
        "modules": ['runtime.compile_cache'],
        "defaults": ["'1'"],
        "read": True, "set": False,
    },
    'RLT_COMPILE_CACHE_EXEC': {
        "modules": ['runtime.compile_cache'],
        "read": True, "set": False,
    },
    'RLT_COST_ANALYSIS': {
        "modules": ['observability.profiler'],
        "read": False, "set": False,
    },
    'RLT_DCN_BLOCK_SIZE': {
        "modules": ['core.trainer'],
        "read": True, "set": False,
    },
    'RLT_DCN_COMPRESSION': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_ELASTIC': {
        "modules": ['runtime.elastic', 'strategies.ray_strategies'],
        "defaults": ["'0'"],
        "read": True, "set": False,
    },
    'RLT_ELASTIC_ACK_TIMEOUT': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_ELASTIC_CONNECT_TIMEOUT': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_ELASTIC_DIR': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_ELASTIC_JOINER': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_ELASTIC_JOIN_TIMEOUT': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_ELASTIC_WAIT': {
        "modules": ['runtime.elastic'],
        "read": False, "set": False,
    },
    'RLT_EVENTS_MAX_BYTES': {
        "modules": ['observability.reqtrace'],
        "read": False, "set": False,
    },
    'RLT_EXECUTABLE_CLS': {
        "modules": ['launchers.utils'],
        "read": True, "set": False,
    },
    'RLT_FAULT': {
        "modules": ['runtime.faults', 'workloads.replay'],
        "read": True, "set": False,
    },
    'RLT_FAULT_FUSE': {
        "modules": ['runtime.faults'],
        "read": False, "set": False,
    },
    'RLT_GLOBAL_RANK': {
        "modules": ['launchers.ray_launcher', 'runtime.elastic', 'runtime.faults', 'strategies.ray_strategies', 'utils.common'],
        "defaults": ["'0'"],
        "read": True, "set": True,
    },
    'RLT_GLOBAL_SEED': {
        "modules": ['core.trainer', 'utils.seed'],
        "defaults": ["'0'"],
        "read": True, "set": False,
    },
    'RLT_HANG_TIMEOUT': {
        "modules": ['strategies.base', 'tune.tune'],
        "read": True, "set": False,
    },
    'RLT_HEARTBEAT_INTERVAL': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_INCIDENT_COOLDOWN_S': {
        "modules": ['observability.incidents'],
        "read": False, "set": False,
    },
    'RLT_INCIDENT_MAX_BUNDLES': {
        "modules": ['observability.incidents'],
        "read": False, "set": False,
    },
    'RLT_LINEAGE_WINDOW_BYTES': {
        "modules": ['observability.lineage'],
        "read": False, "set": False,
    },
    'RLT_LOADER_WORKERS': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_LOCAL_RANK': {
        "modules": ['strategies.ray_strategies'],
        "read": False, "set": True,
    },
    'RLT_MATMUL_PRECISION': {
        "modules": ['utils.precision'],
        "read": False, "set": False,
    },
    'RLT_METRICS_HISTORY': {
        "modules": ['observability.metrics'],
        "read": False, "set": False,
    },
    'RLT_METRIC_TENANT_CARDINALITY': {
        "modules": ['observability.metrics'],
        "read": False, "set": False,
    },
    'RLT_MIN_WORKERS': {
        "modules": ['runtime.elastic', 'strategies.ray_strategies'],
        "defaults": ["'1'"],
        "read": True, "set": False,
    },
    'RLT_NODE_AUTHKEY': {
        "modules": ['runtime.node'],
        "read": True, "set": False,
    },
    'RLT_NODE_IP': {
        "modules": ['runtime.node', 'utils.ports'],
        "read": True, "set": True,
    },
    'RLT_NUM_CPUS': {
        "modules": ['runtime.api', 'tune.tune'],
        "read": True, "set": True,
    },
    'RLT_PAGED_KERNEL': {
        "modules": ['ops.paged_attention'],
        "read": False, "set": False,
    },
    'RLT_PARTITION_RULES': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_PEAK_GBPS': {
        "modules": ['observability.profiler'],
        "read": False, "set": False,
    },
    'RLT_PEAK_TFLOPS': {
        "modules": ['callbacks.throughput'],
        "read": False, "set": False,
    },
    'RLT_PP_MICROBATCHES': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_PP_STAGES': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_PREFETCH_DEPTH': {
        "modules": ['strategies.base'],
        "read": True, "set": False,
    },
    'RLT_PROFILE_AT_STEP': {
        "modules": ['observability.profiler'],
        "read": False, "set": False,
    },
    'RLT_PROFILE_STEPS': {
        "modules": ['observability.profiler'],
        "read": False, "set": False,
    },
    'RLT_PROM_PORT': {
        "modules": ['observability.metrics'],
        "read": False, "set": False,
    },
    'RLT_SANITIZE': {
        "modules": ['analysis.sanitizer'],
        "defaults": ["''"],
        "read": True, "set": False,
    },
    'RLT_SERVE_BLOCK_SIZE': {
        "modules": ['serving.engine'],
        "defaults": ["'16'"],
        "read": True, "set": False,
    },
    'RLT_SERVE_SPECULATE_K': {
        "modules": ['serving.engine'],
        "defaults": ["'0'"],
        "read": True, "set": False,
    },
    'RLT_SLO_ERROR_TARGET': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_SLO_ITL_S': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_SLO_STARVED_S': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_SLO_STEP_S': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_SLO_TENANT_TTFT_S': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_SLO_TTFT_S': {
        "modules": ['observability.slo'],
        "read": False, "set": False,
    },
    'RLT_TELEMETRY': {
        "modules": ['observability.trace', 'strategies.ray_strategies'],
        "read": False, "set": True,
    },
    'RLT_TELEMETRY_DIR': {
        "modules": ['observability.aggregator'],
        "read": False, "set": False,
    },
    'RLT_TELEMETRY_RING': {
        "modules": ['observability.trace'],
        "read": False, "set": False,
    },
    'RLT_TRACE_SAMPLE': {
        "modules": ['observability.reqtrace'],
        "read": False, "set": False,
    },
    'RLT_XLA_CACHE_DIR': {
        "modules": ['runtime.compile_cache', 'strategies.ray_strategies'],
        "read": False, "set": True,
    },
    'RLT_XLA_CACHE_MAX_BYTES': {
        "modules": ['runtime.compile_cache'],
        "read": False, "set": False,
    },
    'RLT_ZERO_QUANTIZED_ALLGATHER': {
        "modules": ['strategies.base'],
        "defaults": ["''"],
        "read": True, "set": False,
    },
    'RLT_ZYGOTE': {
        "modules": ['runtime.api'],
        "read": True, "set": False,
    },
    'RLT_ZYGOTE_AUTHKEY': {
        "modules": ['runtime.zygote'],
        "read": True, "set": True,
    },
    'RLT_ZYGOTE_READY': {
        "modules": ['runtime.zygote'],
        "read": False, "set": False,
    },
}
