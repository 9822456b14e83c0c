"""LightningCLI-equivalent: build a Trainer + LightningModule (+ optional
DataModule and strategy) from command-line flags and/or a YAML config.

Role parity: the reference proves its strategies instantiate from
LightningCLI/jsonargparse configs (reference:
ray_lightning/tests/test_lightning_cli.py:9-27). This is a dependency-free
equivalent: ``--model.lr 0.01 --trainer.max_epochs 3
--strategy.class_name RayStrategy --strategy.num_workers 2`` or
``--config cfg.yaml`` with the same dotted keys.

Also the home of the ``rlt`` operational entry points: ``python -m
ray_lightning_tpu.cli top --dir <run>/telemetry`` renders the driver
aggregator's live summary (see docs/observability.md).
"""
from __future__ import annotations

import argparse
import inspect
from typing import Any, Dict, Optional, Type

from ray_lightning_tpu.core.datamodule import LightningDataModule
from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.core.trainer import Trainer

_STRATEGIES = {}


def _strategy_registry() -> Dict[str, type]:
    global _STRATEGIES
    if not _STRATEGIES:
        from ray_lightning_tpu.strategies.base import SingleDeviceStrategy, XLAStrategy
        from ray_lightning_tpu.strategies.ray_strategies import (
            HorovodRayStrategy,
            RayShardedStrategy,
            RayStrategy,
            RayTPUStrategy,
        )

        _STRATEGIES = {
            "XLAStrategy": XLAStrategy,
            "SingleDeviceStrategy": SingleDeviceStrategy,
            "RayStrategy": RayStrategy,
            "RayTPUStrategy": RayTPUStrategy,
            "RayShardedStrategy": RayShardedStrategy,
            "HorovodRayStrategy": HorovodRayStrategy,
        }
    return _STRATEGIES


def _coerce(value: str) -> Any:
    """Best-effort string -> python value (bool/int/float/str/None).

    Quote a value to force a literal string: ``--model.name '"none"'`` or
    ``--model.version "'1.10'"`` keep the exact text.
    """
    if not isinstance(value, str):
        return value
    if len(value) >= 2 and value[0] == value[-1] and value[0] in ("'", '"'):
        return value[1:-1]
    low = value.lower()
    if low in ("true", "yes"):
        return True
    if low in ("false", "no"):
        return False
    if low in ("none", "null"):
        return None
    for cast in (int, float):
        try:
            return cast(value)
        except ValueError:
            pass
    return value


def _accepts(cls: type, key: str) -> bool:
    try:
        sig = inspect.signature(cls.__init__)
    except (TypeError, ValueError):
        return True
    params = sig.parameters
    return key in params or any(
        p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


class LightningCLI:
    """Parse args, build the components, and (by default) run ``fit``."""

    def __init__(
        self,
        model_class: Type[LightningModule],
        datamodule_class: Optional[Type[LightningDataModule]] = None,
        args: Optional[list] = None,
        run: bool = True,
    ):
        parser = argparse.ArgumentParser(add_help=True)
        parser.add_argument("--config", type=str, default=None,
                            help="YAML file with model/trainer/data/strategy sections")
        known, unknown = parser.parse_known_args(args)

        sections: Dict[str, Dict[str, Any]] = {
            "model": {}, "trainer": {}, "data": {}, "strategy": {},
        }
        if known.config:
            import yaml

            with open(known.config) as f:
                loaded = yaml.safe_load(f) or {}
            for section, content in loaded.items():
                if section in sections and isinstance(content, dict):
                    sections[section].update(content)

        # dotted CLI flags override the config file
        it = iter(unknown)
        for token in it:
            if not token.startswith("--") or "." not in token:
                raise SystemExit(f"unrecognized argument: {token}")
            key = token[2:]
            if "=" in key:
                key, raw = key.split("=", 1)
            else:
                raw = next(it, None)
                if raw is None:
                    raise SystemExit(f"missing value for {token}")
            section, _, field = key.partition(".")
            if section not in sections:
                raise SystemExit(f"unknown section {section!r} in {token}")
            sections[section][field] = _coerce(raw)

        strategy = None
        strat_cfg = dict(sections["strategy"])
        if strat_cfg:
            cls_name = strat_cfg.pop("class_name", "RayStrategy")
            registry = _strategy_registry()
            if cls_name not in registry:
                raise SystemExit(
                    f"unknown strategy {cls_name!r}; options: {sorted(registry)}"
                )
            strategy = registry[cls_name](**strat_cfg)

        model_cfg = dict(sections["model"])
        unknown_keys = [k for k in model_cfg if not _accepts(model_class, k)]
        if unknown_keys:
            sig_params = list(inspect.signature(model_class.__init__).parameters)[1:]
            if len(sig_params) == 1:
                # single-config-dict models (reference MNISTClassifier style)
                self.model = model_class(model_cfg)
            else:
                raise SystemExit(
                    f"unknown --model keys {unknown_keys}; "
                    f"{model_class.__name__} accepts {sig_params}"
                )
        else:
            self.model = model_class(**model_cfg)

        self.datamodule = None
        if datamodule_class is not None:
            bad = [k for k in sections["data"] if not _accepts(datamodule_class, k)]
            if bad:
                raise SystemExit(
                    f"unknown --data keys {bad} for {datamodule_class.__name__}"
                )
            self.datamodule = datamodule_class(**sections["data"])

        trainer_kwargs = dict(sections["trainer"])
        if strategy is not None:
            trainer_kwargs["strategy"] = strategy
        self.trainer = Trainer(**trainer_kwargs)

        if run:
            self.trainer.fit(self.model, datamodule=self.datamodule)


# --------------------------------------------------------------------- #
# operational subcommands
# --------------------------------------------------------------------- #
def _parse_prompt(spec: str) -> list:
    """``"1,2,3"`` -> [1, 2, 3] (the repo has no tokenizer — prompts are
    token ids, same contract as ``models.generation.generate``)."""
    try:
        tokens = [int(t) for t in spec.replace(" ", "").split(",") if t != ""]
    except ValueError:
        raise SystemExit(f"--prompt wants comma-separated token ids, got {spec!r}")
    if not tokens:
        raise SystemExit("--prompt must contain at least one token id")
    return tokens


def _cmd_serve(args) -> int:
    """Stand up a continuous-batching engine on random-init tiny/small
    params and serve token-id prompts (demo + smoke path for the serving
    subsystem; see docs/serving.md)."""
    import dataclasses
    import json
    import time as _time

    from ray_lightning_tpu import observability as _obs

    if args.telemetry:
        _obs.enable()

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    preset = getattr(LlamaConfig, args.preset, None)
    if preset is None:
        raise SystemExit(f"unknown --preset {args.preset!r} (try: tiny, small)")
    cfg = preset()
    if args.fp32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    prompts = [_parse_prompt(p) for p in (args.prompt or [])]
    if args.random_requests:
        import numpy as np

        rng = np.random.default_rng(args.seed)
        for _ in range(args.random_requests):
            plen = int(rng.integers(1, args.max_prompt_len + 1))
            prompts.append(
                [int(t) for t in rng.integers(1, cfg.vocab_size, size=plen)]
            )
    if not prompts:
        raise SystemExit("nothing to serve: pass --prompt and/or --random-requests")
    too_long = [i for i, p in enumerate(prompts) if len(p) > args.max_prompt_len]
    if too_long:
        raise SystemExit(
            f"prompt(s) {too_long} exceed --max-prompt-len {args.max_prompt_len}"
        )

    from ray_lightning_tpu.observability.reqtrace import disposition_for
    from ray_lightning_tpu.serving import RequestShed

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    engine_cfg = EngineConfig(
        num_slots=args.num_slots,
        max_prompt_len=args.max_prompt_len,
        max_len=args.max_len,
        temperature=args.temperature,
        eos_id=args.eos_id,
        seed=args.seed,
        block_size=args.block_size,
    )
    fleet = None
    if args.max_retries > 0 or args.replicas > 1 or args.prefill_replicas > 0:
        # retries, multi-replica routing, and disaggregated prefill/decode
        # all need the request journal: serve through a fleet so a replica
        # fault re-runs the request transparently and prefill-pool engines
        # can ship KV to the decode pool
        from ray_lightning_tpu.serving import LocalReplicaFleet

        try:
            fleet = LocalReplicaFleet(
                lambda: (params, cfg),
                engine_kwargs=dataclasses.asdict(engine_cfg),
                initial_replicas=args.replicas,
                max_retries=args.max_retries,
                prefill_replicas=args.prefill_replicas,
            )
        except ValueError as exc:  # e.g. --prefill-replicas without paged
            raise SystemExit(str(exc))
        engine = fleet._replicas[0]
    else:
        engine = InferenceEngine(params, cfg, engine_cfg)

    t0 = _time.perf_counter()
    completions = []
    shed_rows = []
    submit = fleet.submit if fleet is not None else engine.submit
    for i, p in enumerate(prompts):
        try:
            completions.append(
                submit(
                    p,
                    max_new_tokens=args.max_new_tokens,
                    deadline_ms=args.deadline_ms,
                    priority=args.priority,
                )
            )
        except RequestShed:
            shed_rows.append(
                {
                    "request_id": f"prompt-{i}",
                    "finish_reason": "shed",
                    "disposition": "shed",
                    "retries": 0,
                    "ttft_s": None,
                    "tokens": [],
                }
            )
    if fleet is not None:
        for c in completions:
            try:
                c.result(timeout=300)
            except Exception:
                pass  # disposition reported per-row below
    else:
        engine.run_until_idle()
    wall = _time.perf_counter() - t0

    for c in completions:
        print(
            json.dumps(
                {
                    "request_id": c.request_id,
                    "finish_reason": c.finish_reason,
                    "disposition": (
                        c.disposition
                        if fleet is not None
                        else disposition_for(c.finish_reason)
                    ),
                    "retries": c.retries if fleet is not None else 0,
                    "ttft_s": round(c.ttft_s, 6) if c.ttft_s else None,
                    "tokens": list(c.tokens),
                }
            )
        )
    for row in shed_rows:
        print(json.dumps(row))
    total_tokens = sum(len(c.tokens) for c in completions)
    summary = {
        "requests": len(completions) + len(shed_rows),
        "generated_tokens": total_tokens,
        "shed": len(shed_rows),
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(total_tokens / wall, 2) if wall > 0 else None,
        "slot_utilization": round(engine.slot_utilization(), 4),
        "block_utilization": round(engine.pool.block_utilization(), 4),
        "compile_stats": engine.compile_stats(),
        "pool": engine.pool.stats(),
    }
    if fleet is not None:
        summary["journal"] = fleet.stats()
    print(json.dumps({"summary": summary}))
    if args.cost:
        # second compile of both serving programs; off the serving loop
        print(json.dumps({"cost_summary": engine.cost_summary()}))
    if args.telemetry:
        reg = _obs.registry()
        if reg is not None:
            print(reg.prometheus_text())
        if args.telemetry_dir:
            from ray_lightning_tpu.observability.aggregator import (
                write_local_dump,
            )

            # fleet runs drain every live engine so migrated requests
            # land both their prefill-side and decode-side hop records
            records = (
                fleet.drain_request_records()
                if fleet is not None
                else engine.drain_request_records()
            )
            write_local_dump(
                args.telemetry_dir,
                _obs.get_recorder(),
                reg,
                requests=records,
            )
            print(json.dumps({"telemetry_dir": args.telemetry_dir}))
    if fleet is not None:
        fleet.shutdown()
    else:
        engine.shutdown(drain=False)
    return 0


def _cmd_replay(args) -> int:
    """Play an arrival trace (recorded JSONL or a generator preset)
    against a tenant-aware replica fleet and print/write the verdict
    artifact (see docs/serving.md, "Trace replay")."""
    import dataclasses
    import json
    import os

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import (
        LocalReplicaFleet,
        TenantRegistry,
        parse_tenant_specs,
    )
    from ray_lightning_tpu.workloads import (
        bursty_trace,
        diurnal_trace,
        flash_crowd_trace,
        read_trace,
    )
    from ray_lightning_tpu.workloads.replay import ReplayDriver

    registry = None
    mix = None
    if args.tenants:
        specs = parse_tenant_specs(args.tenants)
        registry = TenantRegistry(specs)
        mix = {s.name: s.weight for s in specs}

    prompt_range = (2, max(2, args.max_prompt_len))
    if os.path.exists(args.trace):
        meta, events = read_trace(args.trace)
        meta = {"source": args.trace, **meta}
    elif args.trace == "diurnal":
        events = diurnal_trace(
            args.duration, args.rps, tenants=mix, seed=args.seed,
            heavy_tail=True, prompt_len=prompt_range,
        )
        meta = {"generator": "diurnal", "seed": args.seed}
    elif args.trace == "bursty":
        events = bursty_trace(
            args.duration, args.rps, tenants=mix, seed=args.seed,
            heavy_tail=True, prompt_len=prompt_range,
        )
        meta = {"generator": "bursty", "seed": args.seed}
    elif args.trace == "flash-crowd":
        crowd = (
            sorted(mix)[-1] if mix else "crowd"
        )  # flood from the LOWEST class (sorted puts best_effort names last
        #    only by luck — prefer an explicit best_effort tenant)
        if registry is not None:
            be = [
                n for n in registry.names()
                if registry.spec(n).tenant_class == "best_effort"
            ]
            if be:
                crowd = be[0]
        events = flash_crowd_trace(
            args.duration, args.rps, crowd_tenant=crowd,
            crowd_at_s=args.duration / 3, tenants=mix, seed=args.seed,
            heavy_tail=True, prompt_len=prompt_range,
        )
        meta = {"generator": "flash-crowd", "crowd": crowd, "seed": args.seed}
    else:
        raise SystemExit(
            f"--trace {args.trace!r}: not a file and not one of "
            "diurnal / bursty / flash-crowd"
        )
    if not events:
        raise SystemExit("trace is empty: raise --duration or --rps")

    preset = getattr(LlamaConfig, args.preset, None)
    if preset is None:
        raise SystemExit(f"unknown --preset {args.preset!r} (try: tiny, small)")
    cfg = dataclasses.replace(preset(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=dict(
            num_slots=args.num_slots,
            max_prompt_len=args.max_prompt_len,
            max_len=args.max_len,
            max_queue=args.max_queue,
        ),
        initial_replicas=args.replicas,
        tenants=registry,
    )
    try:
        verdict = ReplayDriver(
            fleet,
            events,
            tenants=registry,
            speed=args.speed,
            seed=args.seed,
            vocab=int(cfg.vocab_size),
            max_prompt_len=args.max_prompt_len,
            deadline_ms=args.deadline_ms,
            max_wait_ratio=args.max_wait_ratio,
            artifact_path=args.out,
            trace_meta={**meta, "events": len(events)},
        ).run()
    finally:
        fleet.shutdown()
    if args.json:
        print(json.dumps(verdict, indent=2, sort_keys=True))
    else:
        print(
            f"replay: {len(events)} arrivals over "
            f"{verdict['wall_s']}s wall (speed {args.speed}x)  "
            f"goodput_fraction={verdict['goodput']['fraction']}"
        )
        for name, row in sorted(verdict["tenants"].items()):
            att = row.get("slo_attainment")
            print(
                f"  {name:<12} dispatched={row['dispatched']:<5} "
                f"completed={row['completed']:<5} "
                f"quota_rejected={row['quota_rejected']:<4} "
                f"shed={row['shed']:<4} "
                f"ttft_p95={row.get('ttft_p95_s', '-')}s "
                f"slo={att if att is not None else '-'}"
            )
        print(
            f"  starvation: max_wait_ratio="
            f"{verdict['starvation']['max_wait_ratio']} "
            f"(limit {verdict['starvation']['limit']})  "
            f"quota_ok={verdict['quota'].get('ok')}"
        )
        if args.out:
            print(f"  verdict artifact: {args.out}")
        for f in verdict["failures"]:
            print(f"  FAIL: {f}")
    return 0 if verdict["passed"] else 1


def _cmd_profile(args) -> int:
    """Coordinate a fleet profile capture, or render the profile report.

    Without ``--report``: write ``profile_cmd.json`` into the run's
    telemetry directory. Every rank polls the file from its train loop
    and starts ``jax.profiler`` at the same absolute global step
    (``--at-step``, or the cluster's latest step plus ``--lead``).
    With ``--report``: render the cost/capture/attribution tables folded
    into ``summary.json`` by the driver aggregator."""
    import json

    from ray_lightning_tpu.observability import profiler as _profiler
    from ray_lightning_tpu.observability.aggregator import _read_summary

    if args.report:
        print(_profiler.format_profile_report(_read_summary(args.dir)))
        return 0

    start = args.at_step
    if start is None:
        summary = _read_summary(args.dir)
        steps_max = (summary or {}).get("cluster", {}).get("steps_max")
        if steps_max is None:
            if summary is None:
                print(
                    f"no live summary under {args.dir} to anchor the start "
                    "step — pass --at-step N (absolute global step), or "
                    "start the run with RLT_TELEMETRY=1"
                )
            else:
                print(
                    f"summary under {args.dir} has no live worker step "
                    "counter (finished or in-process run) — pass --at-step "
                    "N (absolute global step) to arm a future window"
                )
            return 1
        start = int(steps_max) + args.lead
    cmd = _profiler.write_profile_command(
        args.dir, num_steps=args.steps, start_step=start
    )
    print(
        json.dumps(
            {
                "profile_cmd": f"{args.dir}/{_profiler.PROFILE_CMD_FILE}",
                **cmd,
            }
        )
    )
    return 0


def _cmd_requests(args) -> int:
    """List the slowest finished requests from a run's ``requests.jsonl``
    (written by the driver aggregator / ``serve --telemetry-dir``)."""
    import json
    import os

    from ray_lightning_tpu.observability import reqtrace

    path = os.path.join(args.dir, reqtrace.REQUESTS_FILE)
    records = reqtrace.read_requests(path)
    if not records:
        print(f"no request records found at {path}")
        return 1
    key = args.sort
    records.sort(key=lambda r: (r.get(key) or 0.0), reverse=True)
    if args.limit > 0:
        records = records[: args.limit]
    if args.json:
        for r in records:
            print(json.dumps(r))
        return 0
    cols = (
        ("request_id", 14), ("finish_reason", 8), ("disposition", 11),
        ("retries", 7), ("prompt_len", 6),
        ("tokens_out", 6), ("queue_wait_s", 12), ("prefill_s", 9),
        ("ttft_s", 8), ("total_s", 8), ("itl_p50_ms", 10),
        ("itl_max_ms", 10), ("deferred_ticks", 8), ("replica", 7),
        ("hop", 3), ("pool", 7), ("origin_replica", 6),
    )
    print("  ".join(f"{name:>{w}}" for name, w in cols))
    for r in records:
        cells = []
        for name, w in cols:
            v = r.get(name)
            if isinstance(v, float):
                v = f"{v:.4f}"
            cells.append(f"{'-' if v is None else v:>{w}}")
        print("  ".join(cells))
    return 0


def _cmd_lineage(args) -> int:
    """Render one request's cross-replica causal timeline — prefill hop,
    KV shipment, decode hop, retry branches — stitched from the run's
    ``requests.jsonl`` (see docs/observability.md "Request lineage")."""
    import json
    import os

    from ray_lightning_tpu.observability import lineage as _lineage
    from ray_lightning_tpu.observability import reqtrace

    path = os.path.join(args.dir, reqtrace.REQUESTS_FILE)
    lineages = _lineage.load_lineages(path)
    if not lineages:
        print(f"no request records found at {path}")
        return 1
    if args.rid is None:
        # no rid: list every lineage, multi-hop (migrated/retried) first
        rows = sorted(
            lineages.values(),
            key=lambda lin: (-len(lin.hops), lin.base_rid),
        )
        if args.json:
            for lin in rows:
                print(json.dumps(_lineage.summary(lin), sort_keys=True))
            return 0
        print(
            f"{'base_rid':>14}  {'hops':>4}  {'migr':>4}  {'retry':>5}  "
            f"{'complete':>8}  {'disposition':>11}  {'ttft_s':>8}"
        )
        for lin in rows:
            s = _lineage.summary(lin)
            ttft = s.get("ttft_total_s")
            print(
                f"{lin.base_rid:>14}  {len(lin.hops):>4}  "
                f"{s['migrations']:>4}  {s['retries']:>5}  "
                f"{str(s['complete']):>8}  "
                f"{s.get('disposition') or '-':>11}  "
                f"{f'{ttft:.4f}' if ttft is not None else '-':>8}"
            )
        return 0
    base = reqtrace.base_rid(args.rid)
    lin = lineages.get(base)
    if lin is None:
        print(f"no lineage for rid {args.rid!r} (base {base!r}) in {path}")
        return 1
    if args.json:
        print(json.dumps(_lineage.summary(lin), sort_keys=True))
        return 0
    print(_lineage.render(lin))
    return 0


def _cmd_arbiter(args, parser) -> int:
    """``arbiter status`` prints the ledger's state machine position and
    device split; ``arbiter force-transfer`` queues an operator override
    the live arbiter's next tick executes."""
    import json

    from ray_lightning_tpu.runtime import arbiter as _arbiter

    if args.arbiter_command == "status":
        try:
            led = _arbiter.read_ledger(args.ledger_dir)
        except FileNotFoundError:
            print(f"no arbiter ledger in {args.ledger_dir}")
            return 1
        if args.json:
            print(json.dumps(led, indent=2, sort_keys=True))
            return 0
        owners = {"train": [], "serve": [], "transit": []}
        for dev, side in sorted(led.get("owner", {}).items()):
            owners.setdefault(side, []).append(dev)
        print(f"state:      {led.get('state')}")
        print(f"ledger:     {led.get('ledger')}")
        print(
            f"transfers:  {led.get('transfers_completed')} completed / "
            f"{led.get('transfer_seq')} attempted "
            f"({led.get('failures')} consecutive failures)"
        )
        for side in ("train", "serve", "transit"):
            devs = owners.get(side, [])
            print(f"{side:<8}({len(devs)}): {', '.join(devs) or '-'}")
        tr = led.get("transfer")
        if tr:
            print(
                f"in-flight:  #{tr.get('id')} {tr.get('direction')} "
                f"[{tr.get('phase')}] devices={tr.get('devices')}"
            )
        return 0
    if args.arbiter_command == "force-transfer":
        import os
        import time

        from ray_lightning_tpu.utils.fsio import atomic_write_bytes

        os.makedirs(args.ledger_dir, exist_ok=True)
        path = os.path.join(args.ledger_dir, _arbiter.FORCE_NAME)
        atomic_write_bytes(
            path,
            json.dumps(
                {"direction": args.direction, "ts": time.time()}
            ).encode("utf-8"),
            fsync=True,
        )
        print(f"queued forced {args.direction} transfer at {path}")
        return 0
    parser.print_help()
    return 2


def _cmd_goodput(args) -> int:
    """Render the fleet goodput section folded into ``summary.json`` by
    the driver aggregator: fraction, per-category seconds, and the
    per-source breakdown (docs/observability.md, "Goodput")."""
    import json

    from ray_lightning_tpu.observability.aggregator import _read_summary

    summary = _read_summary(args.dir)
    gp = (summary or {}).get("goodput")
    if not gp:
        print(
            f"no goodput section in the summary under {args.dir} "
            "(needs a run with RLT_TELEMETRY=1 that has reported beats)"
        )
        return 1
    if args.json:
        print(json.dumps(gp, indent=2, sort_keys=True))
        return 0
    total = float(gp.get("total_s") or 0.0)
    print(
        f"goodput fraction: {gp.get('fraction', 0.0):.4f}  "
        f"({total:.1f}s classified wall time across sources)"
    )
    print(f"{'category':<22}{'seconds':>12}{'share':>9}")
    for cat, secs in sorted(
        gp.get("by_category", {}).items(), key=lambda kv: -kv[1]
    ):
        share = (secs / total) if total > 0 else 0.0
        print(f"{cat:<22}{secs:>12.3f}{share:>9.1%}")
    per = gp.get("per_rank", {})
    if per:
        print()
        print(f"{'source':<18}{'wall(s)':>10}{'fraction':>10}  top categories")
        for key, info in sorted(per.items()):
            cats = sorted(
                (info.get("seconds") or {}).items(), key=lambda kv: -kv[1]
            )[:3]
            tops = ", ".join(f"{c} {s:.1f}s" for c, s in cats)
            print(
                f"{key:<18}{info.get('wall_s', 0.0):>10.1f}"
                f"{info.get('fraction', 0.0):>10.4f}  {tops}"
            )
    return 0


def _cmd_incidents(args) -> int:
    """List incident bundles under ``<dir>/incidents/``, or render one
    bundle's contents with ``--show``."""
    import json
    import os
    import time as _time

    from ray_lightning_tpu.observability import incidents as _incidents

    bundles = _incidents.list_bundles(args.dir)
    if args.show is not None:
        match = [b for b in bundles if b["name"] == args.show]
        if not match:
            print(f"no incident bundle named {args.show!r} under {args.dir}")
            return 1
        detail = _incidents.load_bundle(match[0]["path"])
        if args.json:
            print(json.dumps(detail, indent=2, sort_keys=True))
            return 0
        meta = detail.get("incident", {})
        ts = meta.get("ts")
        when = (
            _time.strftime("%Y-%m-%d %H:%M:%S", _time.localtime(ts))
            if ts
            else "-"
        )
        print(f"bundle:  {match[0]['name']}")
        print(f"kind:    {meta.get('kind', '-')}")
        print(f"time:    {when}")
        ev = meta.get("event")
        if ev:
            print(f"trigger: {json.dumps(ev, sort_keys=True)}")
        print("files:")
        for name, info in sorted(detail.get("files", {}).items()):
            bits = ", ".join(f"{k}={v}" for k, v in sorted(info.items()))
            print(f"  {name:<24} {bits}")
        return 0
    if not bundles:
        print(
            "no incident bundles under "
            f"{os.path.join(args.dir, _incidents.INCIDENTS_DIRNAME)}"
        )
        return 1
    if args.json:
        for b in bundles:
            print(json.dumps(b, sort_keys=True))
        return 0
    print(f"{'time':<20}{'kind':<24}{'files':>6}  name")
    for b in bundles:
        when = (
            _time.strftime("%Y-%m-%d %H:%M:%S", _time.localtime(b["ts"]))
            if b.get("ts")
            else "-"
        )
        print(
            f"{when:<20}{b.get('kind', '-'):<24}"
            f"{len(b.get('files', [])):>6}  {b['name']}"
        )
    return 0


def main(argv: Optional[list] = None) -> int:
    """``rlt``-style tool dispatch: ``top`` — live view of a run's
    telemetry directory (summary.json + events.jsonl, written by the
    driver aggregator when ``RLT_TELEMETRY=1``); ``serve`` — stand up a
    continuous-batching inference engine on random-init params and serve
    token-id prompts (docs/serving.md)."""
    parser = argparse.ArgumentParser(prog="rlt")
    sub = parser.add_subparsers(dest="command")
    top = sub.add_parser(
        "top", help="live cluster summary from a run's telemetry directory"
    )
    top.add_argument(
        "--dir",
        required=True,
        help="telemetry directory (e.g. <default_root_dir>/telemetry)",
    )
    top.add_argument(
        "--follow",
        "-f",
        action="store_true",
        help="keep refreshing until interrupted",
    )
    top.add_argument(
        "--interval", type=float, default=2.0, help="refresh period seconds"
    )
    top.add_argument(
        "--serve-port",
        type=int,
        default=None,
        help="also expose the run's metrics.prom at "
        "http://127.0.0.1:PORT/metrics for Prometheus scraping (0 picks "
        "an ephemeral port; see also RLT_PROM_PORT for the in-driver "
        "endpoint)",
    )
    goodput_p = sub.add_parser(
        "goodput",
        help="wall-time goodput breakdown (category seconds + fraction) "
        "from a run's telemetry directory",
    )
    goodput_p.add_argument(
        "--dir",
        required=True,
        help="telemetry directory (e.g. <default_root_dir>/telemetry)",
    )
    goodput_p.add_argument(
        "--json", action="store_true", help="emit the raw goodput section"
    )
    incidents_p = sub.add_parser(
        "incidents",
        help="list or inspect black-box incident bundles captured under "
        "<telemetry>/incidents/",
    )
    incidents_p.add_argument(
        "--dir",
        required=True,
        help="telemetry directory (e.g. <default_root_dir>/telemetry)",
    )
    incidents_p.add_argument(
        "--show",
        default=None,
        metavar="BUNDLE",
        help="inspect one bundle by directory name instead of listing",
    )
    incidents_p.add_argument(
        "--json", action="store_true", help="emit JSON instead of a table"
    )
    serve = sub.add_parser(
        "serve",
        help="continuous-batching inference demo on random-init params",
    )
    serve.add_argument(
        "--prompt",
        action="append",
        help='token-id prompt, e.g. --prompt "1,2,3" (repeatable)',
    )
    serve.add_argument(
        "--random-requests",
        type=int,
        default=0,
        help="additionally submit N random prompts",
    )
    serve.add_argument("--preset", default="tiny", help="LlamaConfig preset")
    serve.add_argument("--num-slots", type=int, default=4)
    serve.add_argument("--max-prompt-len", type=int, default=64)
    serve.add_argument("--max-len", type=int, default=256)
    serve.add_argument("--max-new-tokens", type=int, default=16)
    serve.add_argument(
        "--block-size", type=int, default=None,
        help="KV block size in tokens "
        "(default: RLT_SERVE_BLOCK_SIZE or 16; must divide --max-len)",
    )
    serve.add_argument("--temperature", type=float, default=0.0)
    serve.add_argument("--eos-id", type=int, default=None)
    serve.add_argument("--seed", type=int, default=0)
    serve.add_argument(
        "--deadline-ms", type=float, default=None,
        help="per-request TTL: past it the request is evicted (queued or "
        "decoding) with finish_reason=expired",
    )
    serve.add_argument(
        "--priority", type=int, default=0,
        help="admission class: 0 is never shed; >= 1 is sheddable under "
        "queue pressure or SLO burn",
    )
    serve.add_argument(
        "--replicas", type=int, default=1,
        help="> 1 serves through a multi-replica fleet (request journal + "
        "least-loaded routing)",
    )
    serve.add_argument(
        "--prefill-replicas", type=int, default=0,
        help="> 0 disaggregates the fleet: the first N replicas form the "
        "prefill pool and ship checksummed KV to the decode pool "
        "(requires N < --replicas)",
    )
    serve.add_argument(
        "--max-retries", type=int, default=0,
        help="> 0 serves through the request journal (one-replica fleet): "
        "a replica fault re-runs the request up to this many times",
    )
    serve.add_argument(
        "--fp32", action="store_true", help="force float32 params/activations"
    )
    serve.add_argument(
        "--telemetry",
        action="store_true",
        help="enable spans/metrics and dump the Prometheus text exposition",
    )
    serve.add_argument(
        "--telemetry-dir",
        default=None,
        help="with --telemetry: write trace.json / summary.json / "
        "requests.jsonl to this directory on exit",
    )
    serve.add_argument(
        "--cost",
        action="store_true",
        help="print analytic HLO cost accounting (flops/bytes/collectives) "
        "for the compiled prefill and decode programs",
    )
    replay_p = sub.add_parser(
        "replay",
        help="replay a multi-tenant arrival trace against a replica "
        "fleet and emit the goodput/SLO/fairness verdict artifact",
    )
    replay_p.add_argument(
        "--trace",
        default="flash-crowd",
        help="recorded-trace JSONL path, or a generator preset: "
        "diurnal, bursty, flash-crowd",
    )
    replay_p.add_argument(
        "--duration", type=float, default=30.0,
        help="generated-trace duration in TRACE seconds (presets only)",
    )
    replay_p.add_argument(
        "--rps", type=float, default=4.0,
        help="generated-trace mean/base arrival rate (presets only)",
    )
    replay_p.add_argument(
        "--speed", type=float, default=10.0,
        help="virtual-time acceleration: trace seconds per wall second",
    )
    replay_p.add_argument(
        "--tenants",
        default="gold:guaranteed:4,silver:standard:2,free:best_effort:1",
        help="tenant contracts, comma-separated "
        "name:class[:weight[:rate[:burst]]] (empty string = single-tenant)",
    )
    replay_p.add_argument(
        "--replicas", type=int, default=2, help="fleet size"
    )
    replay_p.add_argument("--preset", default="tiny", help="LlamaConfig preset")
    replay_p.add_argument("--seed", type=int, default=0)
    replay_p.add_argument("--num-slots", type=int, default=4)
    replay_p.add_argument("--max-prompt-len", type=int, default=16)
    replay_p.add_argument("--max-len", type=int, default=32)
    replay_p.add_argument("--max-queue", type=int, default=256)
    replay_p.add_argument(
        "--deadline-ms", type=float, default=None,
        help="per-request TTL threaded into every replayed request",
    )
    replay_p.add_argument(
        "--max-wait-ratio", type=float, default=20.0,
        help="verdict fails when same-priority tenants' mean first-token "
        "waits diverge past this ratio (the starvation bound)",
    )
    replay_p.add_argument(
        "--out", default=None,
        help="write the verdict artifact JSON here (default: print only)",
    )
    replay_p.add_argument(
        "--json", action="store_true",
        help="print the full verdict JSON instead of the summary table",
    )
    profile_p = sub.add_parser(
        "profile",
        help="coordinate a fleet jax.profiler capture, or show the report",
    )
    profile_p.add_argument(
        "--dir",
        required=True,
        help="telemetry directory of the live run "
        "(e.g. <default_root_dir>/telemetry)",
    )
    profile_p.add_argument(
        "--steps", type=int, default=3, help="capture window length in steps"
    )
    profile_p.add_argument(
        "--at-step",
        type=int,
        default=None,
        help="absolute global step to start at (default: the cluster's "
        "latest step from summary.json plus --lead)",
    )
    profile_p.add_argument(
        "--lead",
        type=int,
        default=20,
        help="steps of headroom past the latest observed step, so every "
        "rank sees the command before the window opens",
    )
    profile_p.add_argument(
        "--report",
        action="store_true",
        help="render cost accounting / captures / step-time attribution "
        "from summary.json instead of arming a capture",
    )
    requests_p = sub.add_parser(
        "requests",
        help="slowest finished requests from a run's requests.jsonl",
    )
    requests_p.add_argument(
        "--dir",
        required=True,
        help="telemetry directory containing requests.jsonl",
    )
    requests_p.add_argument(
        "--sort",
        default="ttft_s",
        choices=(
            "ttft_s", "total_s", "queue_wait_s", "deferred_wait_s",
            "prefill_s", "itl_p50_ms", "itl_max_ms", "tokens_out",
        ),
        help="sort key (descending)",
    )
    requests_p.add_argument(
        "--limit", type=int, default=20, help="show at most N requests"
    )
    requests_p.add_argument(
        "--json", action="store_true", help="emit JSONL instead of a table"
    )
    lineage_p = sub.add_parser(
        "lineage",
        help="cross-replica causal timeline for one request "
        "(prefill -> shipment -> decode hops, retry branches)",
    )
    lineage_p.add_argument(
        "--dir",
        required=True,
        help="telemetry directory containing requests.jsonl",
    )
    lineage_p.add_argument(
        "rid",
        nargs="?",
        default=None,
        help="request id (any attempt rid; resolved to its base lineage). "
        "Omit to list all lineages",
    )
    lineage_p.add_argument(
        "--json", action="store_true", help="emit JSON summaries"
    )
    arbiter_p = sub.add_parser(
        "arbiter",
        help="chip-arbiter ledger: transfer state, device split, "
        "operator force-transfer",
    )
    arbiter_sub = arbiter_p.add_subparsers(dest="arbiter_command")
    arbiter_status = arbiter_sub.add_parser(
        "status", help="print the arbiter ledger (state + device split)"
    )
    arbiter_status.add_argument(
        "--ledger-dir",
        required=True,
        help="directory holding arbiter_ledger.json",
    )
    arbiter_status.add_argument(
        "--json", action="store_true", help="emit raw ledger JSON"
    )
    arbiter_force = arbiter_sub.add_parser(
        "force-transfer",
        help="queue an operator-forced transfer for the arbiter's next "
        "tick (bypasses SLO/idle signals, not device floors)",
    )
    arbiter_force.add_argument(
        "--ledger-dir",
        required=True,
        help="directory holding arbiter_ledger.json",
    )
    arbiter_force.add_argument(
        "--direction",
        required=True,
        choices=("borrow", "return"),
        help="borrow = train->serve, return = serve->train",
    )
    args = parser.parse_args(argv)
    if args.command == "top":
        from ray_lightning_tpu.observability.aggregator import render_top

        return render_top(
            args.dir,
            follow=args.follow,
            interval=args.interval,
            serve_port=args.serve_port,
        )
    if args.command == "goodput":
        return _cmd_goodput(args)
    if args.command == "incidents":
        return _cmd_incidents(args)
    if args.command == "serve":
        return _cmd_serve(args)
    if args.command == "replay":
        return _cmd_replay(args)
    if args.command == "profile":
        return _cmd_profile(args)
    if args.command == "requests":
        return _cmd_requests(args)
    if args.command == "lineage":
        return _cmd_lineage(args)
    if args.command == "arbiter":
        return _cmd_arbiter(args, arbiter_p)
    parser.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
