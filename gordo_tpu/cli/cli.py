"""
The ``gordo-tpu`` CLI.

Reference parity: gordo/cli/cli.py — subcommands ``build`` (env-var driven
the way an orchestrated build pod invokes it: ``MACHINE``, ``OUTPUT_DIR``,
``MODEL_REGISTER_DIR``), ``run-server``, and ``workflow`` (see
workflow_generator.py). The build command jinja-expands
``--model-parameter`` values into string model templates, freezes model
defaults by round-tripping the config through the serializer, reports the
built machine, optionally prints CV scores for hyperparameter tuners, and
maps exceptions to exit codes with a JSON report written for the k8s
termination-message path.

(The reference's ``if "err" in machine.name`` crash at cli.py:156-157 is
planted fault code, deliberately not reproduced — SURVEY.md preamble.)
"""

import json
import logging
import os
import sys
import time
import traceback
from typing import Any, List, Optional, Tuple, cast

import click
import jinja2
import yaml

import gordo_tpu
from ..builder.utils import create_model_builder
from .. import serializer
from ..dataset.exceptions import (
    ConfigException,
    InsufficientDataError,
    NoSuitableDataProviderError,
)
from ..dataset.sensor_tag import SensorTagNormalizationError
from ..machine import Machine, load_model_config
from ..reporters.base import ReporterException
from ..server import run_server
from ..client.cli import client_cli
from .custom_types import HostIP, key_value_par
from .exceptions_reporter import ExceptionsReporter, ReportLevel
from .workflow_generator import workflow_cli

_exceptions_reporter = ExceptionsReporter(
    (
        (Exception, 1),
        (ValueError, 2),
        (PermissionError, 20),
        (FileNotFoundError, 30),
        (SensorTagNormalizationError, 60),
        (NoSuitableDataProviderError, 70),
        (InsufficientDataError, 80),
        (ImportError, 85),
        (ReporterException, 90),
        (ConfigException, 100),
    )
)

logger = logging.getLogger(__name__)


@click.group("gordo-tpu")
@click.version_option(version=gordo_tpu.__version__, message=gordo_tpu.__version__)
@click.option(
    "--log-level",
    type=str,
    default="INFO",
    help="Run with custom log-level.",
    envvar="GORDO_LOG_LEVEL",
)
@click.pass_context
def gordo_tpu_cli(gordo_ctx: click.Context, **ctx):
    """The gordo-tpu command line interface."""
    logging.basicConfig(
        level=getattr(logging, str(gordo_ctx.params.get("log_level")).upper()),
        format=(
            "[%(asctime)s] %(levelname)s "
            "[%(name)s.%(funcName)s:%(lineno)d] %(message)s"
        ),
    )
    gordo_ctx.obj = gordo_ctx.params


@click.command()
@click.argument("machine-config", envvar="MACHINE", type=yaml.safe_load)
@click.argument("output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option(
    "--model-register-dir",
    default=None,
    envvar="MODEL_REGISTER_DIR",
    type=click.Path(
        exists=False, file_okay=False, dir_okay=True, writable=True, readable=True
    ),
)
@click.option(
    "--model-builder-class",
    help="ModelBuilder class import path; must subclass "
    "gordo_tpu.builder.build_model.ModelBuilder",
    envvar="MODEL_BUILDER_CLASS",
)
@click.option(
    "--print-cv-scores", help="Prints CV scores to stdout", is_flag=True, default=False
)
@click.option(
    "--model-parameter",
    type=key_value_par,
    multiple=True,
    default=(),
    help="Key-value pair for a model parameter, separated by a comma; may be "
    "given multiple times: --model-parameter key,val",
)
@click.option(
    "--exceptions-reporter-file",
    envvar="EXCEPTIONS_REPORTER_FILE",
    help="JSON output file for exception information",
)
@click.option(
    "--exceptions-report-level",
    type=click.Choice(ReportLevel.get_names(), case_sensitive=False),
    default=ReportLevel.MESSAGE.name,
    envvar="EXCEPTIONS_REPORT_LEVEL",
    help="Detail level for exception reporting",
)
def build(
    machine_config: dict,
    output_dir: str,
    model_register_dir: click.Path,
    model_builder_class: str,
    print_cv_scores: bool,
    model_parameter: List[Tuple[str, Any]],
    exceptions_reporter_file: str,
    exceptions_report_level: str,
):
    """Build a model and deposit it into OUTPUT_DIR."""
    try:
        if model_parameter and isinstance(machine_config["model"], str):
            parameters = dict(model_parameter)
            machine_config["model"] = expand_model(machine_config["model"], parameters)

        machine: Machine = Machine.from_config(
            cast(dict, load_model_config(machine_config)),
            project_name=machine_config["project_name"],
        )

        from ..parallel.mesh import announce_device

        announce_device("build")
        logger.info("Building, output will be at: %s", output_dir)
        logger.info("Register dir: %s", model_register_dir)

        # Round-trip the model config through the serializer so every
        # default parameter is frozen into the stored definition.
        logger.debug("Ensuring the passed model config is fully expanded.")
        machine.model = serializer.into_definition(
            serializer.from_definition(machine.model)
        )

        cls = create_model_builder(model_builder_class)
        builder = cls(machine=machine)

        _, machine_out = builder.build(output_dir, model_register_dir)

        logger.debug("Reporting built machine.")
        machine_out.report()
        logger.debug("Finished reporting.")

        if print_cv_scores:
            for score in get_all_score_strings(machine_out):
                print(score)

    except Exception:
        traceback.print_exc()
        exc_type, exc_value, exc_traceback = sys.exc_info()

        exit_code = _exceptions_reporter.exception_exit_code(exc_type)
        if exceptions_reporter_file:
            _exceptions_reporter.safe_report(
                cast(
                    ReportLevel,
                    ReportLevel.get_by_name(
                        exceptions_report_level, ReportLevel.EXIT_CODE
                    ),
                ),
                exc_type,
                exc_value,
                exc_traceback,
                exceptions_reporter_file,
                # k8s termination messages cap at 2024 bytes; leave headroom
                # for the JSON envelope.
                max_message_len=2024 - 500,
            )
        sys.exit(exit_code)
    else:
        return 0


def expand_model(model_config: str, model_parameters: dict) -> dict:
    """
    Expand a jinja-templated model config string with ``model_parameters``;
    undefined variables are an error.
    """
    try:
        model_template = jinja2.Environment(
            loader=jinja2.BaseLoader(), undefined=jinja2.StrictUndefined
        ).from_string(model_config)
        model_config = model_template.render(**model_parameters)
    except jinja2.exceptions.UndefinedError as e:
        raise ValueError("Model parameter missing value!") from e
    logger.info("Expanded model config: %s", model_config)
    return yaml.safe_load(model_config)


def get_all_score_strings(machine) -> List[str]:
    """
    CV scores as ``{metric}_{fold}={value}`` lines — the stdout format
    hyperparameter tuners (Katib) scrape from the build pod's log.
    """
    all_scores = []
    for (
        metric_name,
        scores,
    ) in machine.metadata.build_metadata.model.cross_validation.scores.items():
        metric_name = metric_name.replace(" ", "-")
        for score_name, score_val in scores.items():
            score_name = score_name.replace(" ", "-")
            all_scores.append(f"{metric_name}_{score_name}={score_val}")
    return all_scores


@click.command("run-server")
@click.option(
    "--host",
    type=HostIP(),
    help="The host to run the server on.",
    default="0.0.0.0",
    envvar="GORDO_SERVER_HOST",
    show_default=True,
)
@click.option(
    "--port",
    type=click.IntRange(1, 65535),
    help="The port to run the server on.",
    default=5555,
    envvar="GORDO_SERVER_PORT",
    show_default=True,
)
@click.option(
    "--workers",
    type=click.IntRange(1, 4),
    help="The number of worker processes for handling requests. Every "
    "worker is a process that initialises the accelerator, and a chip "
    "belongs to one process at a time: leave this at 1 unless each worker "
    "is given a chip of its own. Threads (--threads) share the one "
    "process's device.",
    default=1,
    envvar="GORDO_SERVER_WORKERS",
    show_default=True,
)
@click.option(
    "--worker-connections",
    type=click.IntRange(1, 4000),
    help="The maximum number of simultaneous clients per worker process.",
    default=50,
    envvar="GORDO_SERVER_WORKER_CONNECTIONS",
    show_default=True,
)
@click.option(
    "--threads",
    type=int,
    help="The number of worker threads for handling requests "
    "(only with --worker-class=gthread).",
    default=8,
    envvar="GORDO_SERVER_THREADS",
)
@click.option(
    "--worker-class",
    help="The type of workers to use.",
    default="gthread",
    envvar="GORDO_SERVER_WORKER_CLASS",
    show_default=True,
)
@click.option(
    "--log-level",
    type=click.Choice(["debug", "info", "warning", "error", "critical"]),
    help="The log level for the server.",
    default="debug",
    envvar="GORDO_SERVER_LOG_LEVEL",
    show_default=True,
)
@click.option(
    "--server-app",
    help="The application to run",
    default="gordo_tpu.server.app:build_app()",
    envvar="GORDO_SERVER_APP",
    show_default=True,
)
@click.option(
    "--with-prometheus-config",
    help="Run with custom config for prometheus",
    is_flag=True,
)
@click.option(
    "--batching/--no-batching",
    default=None,
    help="Coalesce concurrent single-model requests into fused fleet "
    "programs (gordo_tpu.serve). Overrides GORDO_TPU_BATCHING; the "
    "default leaves the env switch (default: off) in charge.",
)
@click.option(
    "--batch-max-size",
    type=click.IntRange(1, 4096),
    default=None,
    help="Requests per fused batch before an immediate flush "
    "[GORDO_TPU_BATCH_MAX_SIZE, default 32].",
)
@click.option(
    "--batch-max-delay-ms",
    type=click.FloatRange(0.0, 60000.0),
    default=None,
    help="Longest a request waits for co-batchable traffic "
    "[GORDO_TPU_BATCH_MAX_DELAY_MS, default 5].",
)
@click.option(
    "--batch-queue-depth",
    type=click.IntRange(1, 1 << 20),
    default=None,
    help="Queued requests before admission control answers 429 "
    "[GORDO_TPU_BATCH_QUEUE_DEPTH, default 512].",
)
@click.option(
    "--batch-deadline-ms",
    type=click.FloatRange(1.0, 600000.0),
    default=None,
    help="Per-request batching deadline before a 504 "
    "[GORDO_TPU_BATCH_DEADLINE_MS, default 2000].",
)
@click.option(
    "--batch-row-ladder",
    default=None,
    help="Comma-separated row-padding rungs bounding the jit cache "
    "[GORDO_TPU_BATCH_ROW_LADDER, default 32,128,512,2048,8192].",
)
@click.option(
    "--serve-warmup/--no-serve-warmup",
    default=None,
    help="Precompile each served bucket's ladder programs at startup "
    "[GORDO_TPU_SERVE_WARMUP, default on when batching is on].",
)
@click.option(
    "--serve-precision",
    type=click.Choice(["f32", "bf16", "int8"]),
    default=None,
    help="Default serving precision for the fused batch programs "
    "[GORDO_TPU_SERVE_PRECISION, default f32]. A spec's own "
    "`precision:` field overrides per model; reduced precision serves "
    "only behind a passed precision-parity gate and degrades to f32 "
    "on failure (see docs/serving.md, 'Serving precision').",
)
def run_server_cli(
    host,
    port,
    workers,
    worker_connections,
    threads,
    worker_class,
    log_level,
    server_app,
    with_prometheus_config,
    batching,
    batch_max_size,
    batch_max_delay_ms,
    batch_queue_depth,
    batch_deadline_ms,
    batch_row_ladder,
    serve_warmup,
    serve_precision,
):
    """Run the model server."""
    # Batching knobs travel as env vars — that is how they reach the
    # gunicorn worker processes (and the werkzeug fallback alike).
    for env_name, value in (
        ("GORDO_TPU_BATCHING", None if batching is None else int(batching)),
        ("GORDO_TPU_BATCH_MAX_SIZE", batch_max_size),
        ("GORDO_TPU_BATCH_MAX_DELAY_MS", batch_max_delay_ms),
        ("GORDO_TPU_BATCH_QUEUE_DEPTH", batch_queue_depth),
        ("GORDO_TPU_BATCH_DEADLINE_MS", batch_deadline_ms),
        ("GORDO_TPU_BATCH_ROW_LADDER", batch_row_ladder),
        ("GORDO_TPU_SERVE_WARMUP", None if serve_warmup is None else int(serve_warmup)),
        ("GORDO_TPU_SERVE_PRECISION", serve_precision),
    ):
        if value is not None:
            os.environ[env_name] = str(value)
    config_module = None
    if with_prometheus_config:
        config_module = "gordo_tpu.server.prometheus.gunicorn_config"
    run_server(
        host,
        port,
        workers,
        log_level.lower(),
        config_module=config_module,
        worker_connections=worker_connections,
        threads=threads,
        worker_class=worker_class,
        server_app=server_app,
    )


def _load_fleet_machines(machines_config: str) -> List[Machine]:
    """Machines from a path to (or literal YAML of) a ``machines:``
    document, project_name defaulted per machine — shared by
    ``build-fleet`` and ``plan``."""
    if os.path.isfile(machines_config):
        with open(machines_config) as f:
            config = yaml.safe_load(f)
    else:
        config = yaml.safe_load(machines_config)
    project = config.get("project_name", "fleet-build")
    machine_dicts = [dict(m) for m in config["machines"]]
    for m in machine_dicts:
        m.setdefault("project_name", project)
    return [Machine.from_dict(m) for m in machine_dicts]


def _load_planner_inputs(
    plan_from: Optional[str], cost_table_path: Optional[str]
):
    """(FleetPlan, CostTable) from their CLI paths (None where absent);
    unusable documents (stale version, torn JSON) become clean CLI
    errors, not tracebacks."""
    from ..planner import CostTable, FleetPlan

    try:
        fleet_plan = FleetPlan.load(plan_from) if plan_from else None
    except ValueError as exc:
        raise click.ClickException(f"--plan-from: {exc}") from exc
    try:
        cost_table = (
            CostTable.load(cost_table_path) if cost_table_path else None
        )
    except ValueError as exc:
        raise click.ClickException(f"--cost-table: {exc}") from exc
    return fleet_plan, cost_table


@click.command("plan")
@click.argument("machines-config", envvar="MACHINES_CONFIG")
@click.option(
    "--strategy",
    type=click.Choice(["naive", "packed"]),
    default=None,
    help="Bucket-construction strategy (default: GORDO_TPU_PLAN_STRATEGY "
    "or naive). `packed` is the cost-model bin packer: geometric shape "
    "ladders, per-bucket HBM caps, compile-budget rung merging.",
)
@click.option(
    "--output",
    "-o",
    "output_path",
    default=None,
    type=click.Path(dir_okay=False, writable=True),
    help="Write the FleetPlan JSON here (feed it to "
    "`build-fleet --plan-from`).",
)
@click.option(
    "--cost-table",
    "cost_table_path",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="Calibrated cost_table.json to cost buckets with "
    "(default: the analytic table).",
)
@click.option(
    "--calibrate-from",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="Fit a cost table from this build_trace.jsonl first (the "
    "telemetry trace of any previous build on the same backend) and "
    "plan with it; persisted as cost_table.json beside the trace "
    "unless --cost-table-out is given.",
)
@click.option(
    "--cost-table-out",
    default=None,
    type=click.Path(dir_okay=False, writable=True),
    help="Where --calibrate-from persists the fitted table.",
)
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw plan document instead of the table",
)
def plan_fleet(
    machines_config: str,
    strategy: Optional[str],
    output_path: Optional[str],
    cost_table_path: Optional[str],
    calibrate_from: Optional[str],
    cost_table_out: Optional[str],
    as_json: bool,
):
    """
    Emit and explain the FleetPlan a ``build-fleet`` of MACHINES_CONFIG
    would run: every bucket with its member roster, padded shape,
    predicted compile/run seconds, HBM footprint and padding waste —
    deterministic (same config + cost table → byte-identical JSON, so
    the plan hash is a stable identity the build journal records).

    Data IS fetched and staged (bucket shapes depend on per-machine
    sample counts), but nothing trains and no artifacts are written.
    """
    from ..parallel.fleet_build import FleetBuilder
    from ..planner import COST_TABLE_FILE, calibrate, render_plan

    _, cost_table = _load_planner_inputs(None, cost_table_path)
    if calibrate_from:
        cost_table = calibrate(calibrate_from, cost_table)
        table_path = cost_table_out or os.path.join(
            os.path.dirname(os.path.abspath(calibrate_from)), COST_TABLE_FILE
        )
        cost_table.save(table_path)
        logger.info("Calibrated cost table written to %s", table_path)

    machines = _load_fleet_machines(machines_config)
    builder = FleetBuilder(
        machines, plan_strategy=strategy, cost_table=cost_table
    )
    plan = builder.plan_only()
    if builder.build_errors:
        name, exc = next(iter(builder.build_errors.items()))
        raise click.ClickException(
            f"{len(builder.build_errors)} machine(s) could not be planned "
            f"(first: {name}: {exc!r})"
        )
    if output_path:
        plan.save(output_path)
        logger.info("FleetPlan written to %s", output_path)
    if as_json:
        click.echo(plan.to_json(), nl=False)
    else:
        click.echo(render_plan(plan))


@click.command("build-fleet")
@click.argument("machines-config", envvar="MACHINES_CONFIG")
@click.argument("output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option(
    "--model-register-dir",
    default=None,
    envvar="MODEL_REGISTER_DIR",
    type=click.Path(
        exists=False, file_okay=False, dir_okay=True, writable=True, readable=True
    ),
)
@click.option(
    "--exceptions-reporter-file",
    envvar="EXCEPTIONS_REPORTER_FILE",
    help="JSON output file for exception information",
)
@click.option(
    "--exceptions-report-level",
    type=click.Choice(ReportLevel.get_names(), case_sensitive=False),
    default=ReportLevel.MESSAGE.name,
    envvar="EXCEPTIONS_REPORT_LEVEL",
    help="Detail level for exception reporting",
)
@click.option(
    "--resume",
    is_flag=True,
    envvar="FLEET_RESUME",
    help="Resume a crashed build from OUTPUT_DIR's build journal: machines "
    "journaled complete (config-hash matched, artifact checksum-verified) "
    "are skipped; only the remainder is replanned and trained.",
)
@click.option(
    "--plan-strategy",
    type=click.Choice(["naive", "packed"]),
    default=None,
    help="Bucket-construction strategy (gordo_tpu.planner): naive = the "
    "historical exact-key grouping (default, also via "
    "GORDO_TPU_PLAN_STRATEGY), packed = cost-model bin packing with "
    "geometric shape ladders, HBM caps and a compile budget.",
)
@click.option(
    "--plan-from",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="Replay a FleetPlan emitted by `gordo-tpu plan`: covered "
    "members train in their planned buckets with their planned pad "
    "targets (stable across --resume); uncovered members pack live.",
)
@click.option(
    "--cost-table",
    "cost_table_path",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="Calibrated cost_table.json for the packed strategy's cost "
    "model.",
)
def build_fleet(
    machines_config: str,
    output_dir: str,
    model_register_dir: Optional[str],
    exceptions_reporter_file: str,
    exceptions_report_level: str,
    resume: bool,
    plan_strategy: Optional[str],
    plan_from: Optional[str],
    cost_table_path: Optional[str],
):
    """
    Train a whole machine shard as mesh-sharded model batches on this TPU
    slice — the entry point each fleet-builder Job pod runs (the TPU-native
    replacement for the reference's one-`build`-pod-per-machine fan-out).

    MACHINES_CONFIG is a path to (or literal YAML of) a document with a
    ``machines:`` list of fully-resolved machine dicts, as emitted into the
    workflow's ConfigMaps by ``workflow generate``.
    """
    import os
    import time

    # the build records what the command does before and after it as
    # phases of its own: config_load from here, report at its end
    started = time.perf_counter()
    try:
        # after the distributed handshake: announcing initialises the
        # backend, and jax.distributed must be up before that
        _maybe_init_distributed()
        from ..parallel.mesh import announce_device

        announce_device("build-fleet")

        # ConfigMap dicts from `workflow generate` are fully resolved; a
        # hand-written document may instead carry project_name at the top
        # level (or omit it entirely for local runs).
        machines = _load_fleet_machines(machines_config)
        fleet_plan, cost_table = _load_planner_inputs(
            plan_from, cost_table_path
        )

        from ..parallel.fleet_build import FleetBuilder

        # On a multi-host slice every process runs the same SPMD training
        # program, but only the coordinator may write artifacts, touch the
        # shared build cache, or run reporters — otherwise N pods race on
        # the same files and duplicate every report.
        is_coordinator = int(os.getenv("JAX_PROCESS_INDEX", "0")) == 0
        if not is_coordinator:
            # The coordinator's machine filters must be mirrored here: all
            # processes run ONE SPMD program, so every process has to
            # train the same surviving machine set — a divergent list
            # desynchronizes the collective device programs. Both mirrors
            # read the shared volume without writing anything.
            if resume:
                from ..parallel.journal import resumable_names

                skip = set(resumable_names(output_dir, machines))
                machines = [m for m in machines if m.name not in skip]
            if model_register_dir:
                # read-only shadow of FleetBuilder.build's cache-hit
                # filter (load_cached runs on the coordinator only);
                # probe_cache shares check_cache's validity definition
                from ..builder.build_model import ModelBuilder

                machines = [
                    m
                    for m in machines
                    if ModelBuilder.probe_cache(m, model_register_dir) is None
                ]
        logger.info(
            "Fleet-building %d machines; output at %s%s",
            len(machines),
            output_dir,
            "" if is_coordinator else " (non-coordinator: side effects skipped)",
        )
        builder = FleetBuilder(
            machines,
            plan_strategy=plan_strategy,
            fleet_plan=fleet_plan,
            cost_table=cost_table,
        )
        results = builder.build(
            output_dir if is_coordinator else None,
            model_register_dir=model_register_dir if is_coordinator else None,
            resume=resume,
            started=started,
            report=is_coordinator,
        )
        logger.info(
            "Fleet build complete: %d built, %d resumed (skipped), %d failed; "
            "contained device faults: %s",
            len(results),
            len(builder.resumed),
            len(builder.build_errors),
            {k: v for k, v in builder.robustness.items() if v} or "none",
        )
        if builder.build_errors:
            # failFast:false — successes are saved/reported above; exit with
            # the first failure's mapped code like a reference builder pod.
            name, exc = next(iter(builder.build_errors.items()))
            raise exc
    except Exception:
        traceback.print_exc()
        exc_type, exc_value, exc_traceback = sys.exc_info()
        exit_code = _exceptions_reporter.exception_exit_code(exc_type)
        if exceptions_reporter_file:
            _exceptions_reporter.safe_report(
                cast(
                    ReportLevel,
                    ReportLevel.get_by_name(
                        exceptions_report_level, ReportLevel.EXIT_CODE
                    ),
                ),
                exc_type,
                exc_value,
                exc_traceback,
                exceptions_reporter_file,
                max_message_len=2024 - 500,
            )
        sys.exit(exit_code)


def _maybe_init_distributed():
    """
    Join the slice-wide jax.distributed mesh when launched as one pod of a
    multi-host fleet-builder Job (env injected by the workflow template).
    """
    import os

    process_count = int(os.getenv("JAX_PROCESS_COUNT", "1"))
    if process_count > 1:
        import jax

        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
            num_processes=process_count,
            process_id=int(os.environ["JAX_PROCESS_INDEX"]),
        )
        logger.info(
            "jax.distributed initialized: process %s of %s",
            os.environ["JAX_PROCESS_INDEX"],
            process_count,
        )


@click.command("build-status")
@click.argument("output-dir", envvar="OUTPUT_DIR")
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw build_status.json document instead of the table",
)
@click.option(
    "--watch",
    default=None,
    type=float,
    help="Re-render every N seconds until the build leaves 'running'",
)
def build_status(output_dir: str, as_json: bool, watch: Optional[float]):
    """
    Render the live progress of a fleet build from OUTPUT_DIR's
    ``build_status.json`` heartbeat — the chip-fan-out analog of
    ``argo get``: state, current phase, machine counts with an ETA from
    the completed-machine rate, and the per-phase wall-clock table.

    Works mid-build (the builder atomically replaces the document on
    every phase transition and machine completion), after a crash (the
    last heartbeat survives beside the journal for post-mortems), and
    on finished builds. The model server exposes the same document at
    ``/gordo/v0/<project>/build-status``.
    """
    import time as time_mod

    from ..telemetry import load_status, render_status

    while True:
        doc = load_status(output_dir)
        if doc is None:
            raise click.ClickException(
                f"No build status found in {output_dir} (no fleet build "
                "has written a heartbeat there, or telemetry is disabled)"
            )
        if as_json:
            click.echo(json.dumps(doc, indent=1, sort_keys=True))
        else:
            click.echo(render_status(doc))
        if watch is None or doc.get("state") != "running":
            break
        time_mod.sleep(max(0.1, watch))
        click.echo("")


@click.command("fleet-status")
@click.argument("directory", envvar="OUTPUT_DIR")
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw joined document instead of the table",
)
@click.option(
    "--watch",
    default=None,
    type=float,
    help="Re-render every N seconds (Ctrl-C to stop)",
)
@click.option(
    "--machines",
    "machines",
    default=None,
    help="Per-machine record selection: `all`, `none`, a state "
    "(`healthy`/`degraded`/`drifting`/`quarantined`/`unhealthy`) or a "
    "comma-separated name list. Default: inline while the fleet is "
    "small, summary + top-K offenders past "
    "GORDO_TPU_FLEET_STATUS_MAX_MACHINES.",
)
@click.option(
    "--limit",
    default=None,
    type=int,
    help="Page size for --machines selections (capped at "
    "GORDO_TPU_FLEET_STATUS_MAX_MACHINES)",
)
@click.option(
    "--offset",
    default=0,
    type=int,
    help="Page offset for --machines selections",
)
def fleet_status(
    directory: str,
    as_json: bool,
    watch: Optional[float],
    machines: Optional[str],
    limit: Optional[int],
    offset: int,
):
    """
    The fleet console: ONE joined operator view over DIRECTORY (a build
    output / served revision dir) — build progress
    (``build_status.json``), plan accuracy incl. the measured
    HBM/padding actuals (``fleet_plan.json`` + the health ledger),
    per-member health counts with the unhealthiest machines
    (``fleet_health.json``), lifecycle phase and quarantine records
    (``.lifecycle/state.json``), device memory occupancy and
    compile-cache hit rates.

    The model server answers the same document at
    ``/gordo/v0/<project>/fleet-health`` — point this CLI at the
    artifact volume, or curl the route for a live serving process's
    in-memory view (its device counters see the serving programs).
    """
    import time as time_mod

    from ..stream import stream_plane_section
    from ..telemetry import (
        fleet_status_document,
        render_fleet_status,
        utilization_snapshot,
    )

    if not os.path.isdir(directory):
        raise click.ClickException(f"No such directory: {directory}")
    while True:
        doc = fleet_status_document(
            directory,
            device=utilization_snapshot(),
            # None in a CLI process with no installed plane — the
            # section is injected, never imported by telemetry
            stream=stream_plane_section(),
            machines=machines,
            limit=limit,
            offset=offset,
        )
        if as_json:
            click.echo(json.dumps(doc, indent=1, sort_keys=True, default=str))
        else:
            click.echo(render_fleet_status(doc))
        if watch is None:
            break
        time_mod.sleep(max(0.1, watch))
        click.echo("")


def _parse_since(
    since: Optional[str], last: Optional[str]
) -> Optional[float]:
    """``--since`` (ISO timestamp or epoch seconds) / ``--last``
    (duration like ``90m``/``6h``/``7d``) -> an epoch cutoff."""
    from ..telemetry.aggregate import parse_span_time
    from ..telemetry.slo import parse_duration

    if since and last:
        raise click.ClickException("--since and --last are exclusive")
    if last:
        try:
            return time.time() - parse_duration(last)
        except ValueError as exc:
            raise click.ClickException(str(exc))
    if since:
        try:
            return float(since)
        except ValueError:
            pass
        ts = parse_span_time(since)
        if ts is None:
            raise click.ClickException(
                f"Unparseable --since {since!r} (ISO timestamp or epoch)"
            )
        return ts
    return None


@click.command("trace")
@click.argument("target", envvar="OUTPUT_DIR")
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw analysis document instead of the report",
)
@click.option(
    "--since",
    default=None,
    help="Only analyze spans ending at/after this ISO timestamp (or "
    "epoch seconds); rotated generations older than the cutoff are "
    "skipped without being parsed.",
)
@click.option(
    "--last",
    default=None,
    help="Only analyze the trailing window, e.g. `--last 1h`, `90m`, "
    "`7d` (exclusive with --since).",
)
def trace(target: str, as_json: bool, since: Optional[str], last: Optional[str]):
    """
    Analyze a span trace: per-span latency percentiles, the request
    per-stage breakdown with attribution coverage and the median
    request's critical path, and the top self-time frames the sampling
    profiler collected.

    TARGET is a trace file (``serve_trace.jsonl`` / ``build_trace.jsonl``,
    rotated generations are read automatically) or a directory holding
    one — a serving telemetry dir or a build output dir. Per-worker
    sink variants (``serve_trace-<pid>.jsonl``) are read-merged into
    one analysis per logical trace; with both serve and build traces
    present, each is analyzed in turn.
    """
    from ..telemetry import SERVE_TRACE_FILE
    from ..telemetry.aggregate import sink_window_index
    from ..telemetry.progress import BUILD_TRACE_FILE
    from ..telemetry.trace_analysis import (
        analyze_trace,
        render_analysis,
        trace_bases,
    )

    since_ts = _parse_since(since, last)
    window_index: dict = {}
    if os.path.isdir(target):
        # one analysis per LOGICAL trace: all worker variants of the
        # serve trace merge, ditto the build trace
        groups = [
            bases
            for bases in (
                trace_bases(target, SERVE_TRACE_FILE),
                trace_bases(target, BUILD_TRACE_FILE),
            )
            if bases
        ]
        if since_ts is not None:
            # the rollup manifest records each rotated generation's span
            # window — skip-by-window beats the mtime heuristic (a
            # late-touched old generation still gets skipped)
            window_index = sink_window_index(target)
        if not groups:
            raise click.ClickException(
                f"No {SERVE_TRACE_FILE} or {BUILD_TRACE_FILE} in {target} "
                "(is GORDO_TPU_TELEMETRY_DIR pointed elsewhere, or "
                "telemetry disabled?)"
            )
    elif os.path.exists(target):
        groups = [[target]]
    else:
        raise click.ClickException(f"No such trace file or directory: {target}")

    docs = [
        analyze_trace(group, since_ts=since_ts, window_index=window_index)
        for group in groups
    ]
    if as_json:
        click.echo(
            json.dumps(docs[0] if len(docs) == 1 else docs, indent=1)
        )
        return
    for i, doc in enumerate(docs):
        if i:
            click.echo("")
        click.echo(render_analysis(doc))


@click.group("slo")
def slo_cli():
    """Fleet SLO engine: cross-worker rollups, error budgets, and
    multi-window burn-rate alerts (gordo_tpu.telemetry.slo;
    docs/observability.md "SLOs & error budgets")."""


def _slo_evaluate(directory: str, config_path: Optional[str]):
    from ..telemetry import slo as slo_engine

    if not os.path.isdir(directory):
        raise click.ClickException(f"No such directory: {directory}")
    try:
        config = slo_engine.load_slo_config(directory, path=config_path)
    except (OSError, ValueError) as exc:
        raise click.ClickException(f"Bad SLO config: {exc}")
    try:
        return slo_engine.evaluate(directory, config=config)
    except OSError as exc:
        raise click.ClickException(f"SLO evaluation failed: {exc}")


@slo_cli.command("status")
@click.argument("directory", envvar="GORDO_TPU_TELEMETRY_DIR")
@click.option(
    "--config",
    "config_path",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="slos.toml to evaluate against (default: GORDO_TPU_SLO_CONFIG, "
    "then DIRECTORY/slos.toml, then the packaged defaults).",
)
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw status document instead of the table",
)
@click.option(
    "--watch",
    default=None,
    type=float,
    help="Re-evaluate and re-render every N seconds (Ctrl-C to stop)",
)
def slo_status(
    directory: str,
    config_path: Optional[str],
    as_json: bool,
    watch: Optional[float],
):
    """
    Evaluate and render the SLO status of DIRECTORY (a telemetry dir or
    build output dir holding trace sinks): per-objective error-budget
    remaining, multi-window burn rates, and every alert's state in the
    pending -> firing -> resolved lifecycle.

    Evaluation is incremental — new spans fold into the persisted
    ``rollups/`` artifacts; re-running over an unchanged corpus reads
    zero span bytes. The model server answers the same document at
    ``/gordo/v0/<project>/slo``.
    """
    from ..telemetry import render_slo_status

    while True:
        doc = _slo_evaluate(directory, config_path)
        if as_json:
            click.echo(json.dumps(doc, indent=1, sort_keys=True, default=str))
        else:
            click.echo(render_slo_status(doc))
        if watch is None:
            break
        time.sleep(max(0.1, watch))
        click.echo("")


@slo_cli.command("check")
@click.argument("directory", envvar="GORDO_TPU_TELEMETRY_DIR")
@click.option(
    "--config",
    "config_path",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="slos.toml to evaluate against (default resolution as `status`).",
)
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw status document instead of the table",
)
def slo_check(directory: str, config_path: Optional[str], as_json: bool):
    """
    The SLO gate: evaluate DIRECTORY and exit non-zero while any
    burn-rate alert is FIRING (pending and resolved alerts exit 0), so
    deploy pipelines and cron monitors can gate on one command.
    """
    from ..telemetry import render_slo_status

    doc = _slo_evaluate(directory, config_path)
    if as_json:
        click.echo(json.dumps(doc, indent=1, sort_keys=True, default=str))
    else:
        click.echo(render_slo_status(doc))
    if doc.get("firing"):
        raise SystemExit(1)


@click.command("lint")
@click.argument("paths", nargs=-1)
@click.option(
    "--root",
    "root",
    default=None,
    type=click.Path(exists=True, file_okay=False),
    help="Repository root the paths (and the baseline) are relative to "
    "(default: the current directory).",
)
@click.option(
    "--baseline",
    "baseline_path",
    default=None,
    type=click.Path(dir_okay=False),
    help="Baseline file of grandfathered findings (default: "
    "<root>/lint_baseline.json; every entry must carry a justification).",
)
@click.option(
    "--update-baseline",
    is_flag=True,
    help="Rewrite the baseline to cover every current finding (each "
    "entry gets a FIXME justification to hand-edit), then exit 0.",
)
@click.option(
    "--report-only",
    is_flag=True,
    help="Always exit 0: print the findings, never gate (CI visibility "
    "mode).",
)
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw lint document instead of the report",
)
@click.option(
    "--sarif",
    "sarif_path",
    default=None,
    type=click.Path(dir_okay=False),
    help="Also write a SARIF 2.1.0 document to this path (rule "
    "metadata, stable fingerprints, baseline entries as suppressions) "
    "— the artifact the CI lint job uploads for PR annotations.",
)
def lint(
    paths: Tuple[str, ...],
    root: Optional[str],
    baseline_path: Optional[str],
    update_baseline: bool,
    report_only: bool,
    as_json: bool,
    sarif_path: Optional[str],
):
    """
    The invariant gate: run the project's static-analysis rules
    (gordo_tpu.analysis — layering arrows, JAX dispatch hazards, the
    env-knob registry, atomic artifact writes, clock discipline,
    Prometheus label cardinality) over PATHS (default: ``gordo_tpu/``)
    and exit non-zero on any finding that is neither suppressed in-file
    (``# gt-lint: disable=<rule>``) nor grandfathered in the committed
    baseline. See ``docs/static-analysis.md`` for the rule catalog.

    Example: ``gordo-tpu lint`` at the repo root — the same invocation
    the CI ``lint`` job and ``make lint-gordo`` run.
    """
    from ..analysis import (
        BaselineError,
        default_baseline_path,
        default_rules,
        lint_document,
        load_baseline,
        render_report,
        run_lint,
        sarif_document,
        split_by_baseline,
        write_baseline,
    )

    root = os.path.abspath(root or os.getcwd())
    if baseline_path is None:
        baseline_path = default_baseline_path(root)
    rules = default_rules()
    result = run_lint(root, rules, paths=list(paths) or None)
    if update_baseline:
        # still-matching entries keep their hand-written justifications;
        # an unreadable existing baseline just means a fresh start
        try:
            existing = load_baseline(baseline_path)
        except BaselineError:
            existing = []
        write_baseline(
            baseline_path,
            result.findings,
            "FIXME: justify this grandfathered finding (lint refuses "
            "unjustified baselines)",
            existing=existing,
        )
        click.echo(
            f"Baseline rewritten with {len(result.findings)} entr"
            f"{'y' if len(result.findings) == 1 else 'ies'} -> "
            f"{baseline_path}; edit the justifications before committing."
        )
        return
    try:
        entries = load_baseline(baseline_path)
    except BaselineError as exc:
        raise click.ClickException(str(exc))
    new, baselined, stale = split_by_baseline(result.findings, entries)
    if sarif_path:
        import gordo_tpu

        doc = sarif_document(
            result,
            new,
            baselined,
            entries=entries,
            rules=rules,
            version=gordo_tpu.__version__,
        )
        tmp = f"{sarif_path}.tmp-{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as handle:
            json.dump(doc, handle, indent=1, sort_keys=True)
            handle.write("\n")
        os.replace(tmp, sarif_path)
    if as_json:
        click.echo(
            json.dumps(
                lint_document(result, new, baselined, stale),
                indent=1,
                sort_keys=True,
            )
        )
    else:
        click.echo(render_report(result, new, baselined, stale))
    if (new or result.parse_errors) and not report_only:
        raise SystemExit(1)


@click.command("lockgraph")
@click.argument("sinks", nargs=-1, required=True)
@click.option(
    "--top",
    default=10,
    type=int,
    help="Held-while-blocking hotspot rows to report.",
)
@click.option(
    "--report-only",
    is_flag=True,
    help="Always exit 0: print the report, never gate.",
)
@click.option(
    "--as-json",
    "as_json",
    is_flag=True,
    help="Print the raw analysis document instead of the report.",
)
def lockgraph(sinks: Tuple[str, ...], top: int, report_only: bool, as_json: bool):
    """
    Analyze lock-order trace sinks for deadlock potential: build the
    acquisition-ordering graph recorded by ``GORDO_TPU_LOCK_TRACE``
    (``gordo_tpu.analysis.lockgraph``), fail on any ordering cycle —
    two threads taking the same locks in opposite orders — and report
    the max-held-while-blocking hotspots.

    SINKS are edge files (``lock_trace-<pid>.jsonl``) or glob patterns;
    a traced multi-process run merges into one graph.

    Example: ``GORDO_TPU_LOCK_TRACE=1 pytest -m "serve or slo" &&
    gordo-tpu lockgraph 'lock_trace-*.jsonl'``
    """
    import glob as _glob

    from ..analysis.lockgraph import analyze, render_report as render_lock_report

    paths: list = []
    for pattern in sinks:
        matched = sorted(_glob.glob(pattern))
        paths.extend(matched if matched else [pattern])
    missing = [p for p in paths if not os.path.exists(p)]
    if missing or not paths:
        raise click.ClickException(
            "no trace sinks found: "
            + (", ".join(missing) or "(empty sink list)")
            + " — run the suites with GORDO_TPU_LOCK_TRACE set first"
        )
    report = analyze(paths, top=top)
    if as_json:
        click.echo(json.dumps(report, indent=1, sort_keys=True))
    else:
        click.echo(render_lock_report(report))
    if not report["ok"] and not report_only:
        raise SystemExit(1)


@click.command("wait-for-models")
@click.argument("models-dir", envvar="MODELS_DIR")
@click.option(
    "--name",
    "names",
    multiple=True,
    help="Model names to wait for; repeatable. Default: EXPECTED_MODELS env",
)
@click.option("--timeout", default=3600, type=int, envvar="WAIT_TIMEOUT")
@click.option("--poll-interval", default=10, type=int)
def wait_for_models(
    models_dir: str, names: Tuple[str, ...], timeout: int, poll_interval: int
):
    """
    Block until every named model's artifacts exist under MODELS_DIR.

    The plain-k8s stand-in for the reference DAG's step ordering (its
    client/cleanup steps depend on builder steps): replay and
    revision-cleanup Jobs run this in an initContainer so they start only
    after the fleet builders have written the revision.
    """
    import os
    import time as time_mod

    if not names:
        names = tuple(yaml.safe_load(os.getenv("EXPECTED_MODELS", "[]")) or ())
    if not names:
        raise click.ClickException("No model names given (--name / EXPECTED_MODELS)")

    deadline = time_mod.monotonic() + timeout
    missing = set(names)
    while missing:
        missing = {
            name
            for name in missing
            if not os.path.isfile(os.path.join(models_dir, name, "metadata.json"))
        }
        if not missing:
            break
        if time_mod.monotonic() > deadline:
            raise click.ClickException(
                f"Timed out after {timeout}s waiting for models: "
                f"{', '.join(sorted(missing)[:10])}"
            )
        logger.info("Waiting for %d model(s)...", len(missing))
        time_mod.sleep(poll_interval)
    click.echo(f"All {len(names)} models present in {models_dir}")


@click.command("score")
@click.argument("model-dir", type=click.Path(exists=True, file_okay=False))
@click.argument("output", type=click.Path(dir_okay=False, writable=True))
@click.option("--input", "input_path", default=None, type=click.Path(exists=True),
              help="Parquet/CSV of sensor columns to score (overrides --start/--end)")
@click.option("--start", default=None, help="Score window start (ISO timestamp)")
@click.option("--end", default=None, help="Score window end (ISO timestamp)")
@click.option(
    "--anomaly/--predict-only",
    "with_anomaly",
    default=True,
    help="Emit the full anomaly frame (detector models) or raw predictions",
)
def score(
    model_dir: str,
    output: str,
    input_path: Optional[str],
    start: Optional[str],
    end: Optional[str],
    with_anomaly: bool,
):
    """
    Batch-score a data window against a built model, no server needed —
    backfills, migrations, ad-hoc investigations. Data comes from a
    parquet/CSV file (``--input``) or from the machine's own dataset
    config re-pointed at ``--start``/``--end`` (as the replay client
    does). Output is one parquet of the anomaly frame (or raw
    predictions) with pipe-flattened columns, the replay sink's format.

    Long series on a multi-device host score through the ring
    (time-sharded) path automatically: windowed models shard the time
    axis over the mesh past ``GORDO_TPU_RING_PREDICT_ROWS`` rows
    (parallel/sequence.py) — the host never materializes the lookback×
    window blowup of a year-scale backfill.
    """
    import jax
    import pandas as pd

    from .. import serializer
    from ..client.forwarders import flatten_columns
    from ..dataset import GordoBaseDataset

    model = serializer.load(model_dir)
    metadata = serializer.load_metadata(model_dir)

    if input_path:
        if input_path.endswith(".csv"):
            X = pd.read_csv(input_path, index_col=0, parse_dates=True)
        else:
            X = pd.read_parquet(input_path)
        y = X  # file mode carries inputs only; autoencoder semantics
    else:
        if not (start and end):
            raise click.ClickException("Provide --input or both --start/--end")
        dataset_config = dict(metadata.get("dataset") or {})
        if not dataset_config:
            raise click.ClickException(
                "Model metadata carries no dataset config; use --input"
            )
        dataset_config["train_start_date"] = start
        dataset_config["train_end_date"] = end
        # the dataset yields the machine's own targets, so machines with a
        # distinct target_tag_list score against the right columns
        X, y = GordoBaseDataset.from_dict(dataset_config).get_data()

    logger.info("Scoring %d rows on %d device(s)", len(X), len(jax.devices()))
    if with_anomaly and hasattr(model, "anomaly"):
        frame = model.anomaly(X, y)
    else:
        values = model.predict(X)
        index = X.index[len(X) - len(values):]
        frame = pd.DataFrame(
            values, index=index, columns=[str(i) for i in range(values.shape[1])]
        )
    flatten_columns(frame).to_parquet(output)
    click.echo(f"Scored {len(frame)} rows -> {output}")


@click.command("ensure-single-workflow")
@click.argument("models-root", envvar="MODELS_ROOT")
@click.argument("revision", envvar="PROJECT_REVISION")
@click.option(
    "--check-only", is_flag=True, help="Verify the lock without acquiring it"
)
def ensure_single_workflow(models_root: str, revision: str, check_only: bool):
    """
    Single-deployer guard on the shared model volume.

    The reference's ensure-single-workflow Argo step kills OLDER concurrent
    workflows of the same project before deploying
    (argo-workflow.yml.template:47-104). This plane has no k8s API access
    (by design — no kubectl, no RBAC), so the semantics invert: the STALE
    deploy aborts itself. The lock file ``MODELS_ROOT/deploy.lock`` records
    the newest deploying revision (atomic rename); any Job belonging to an
    older revision fails this guard fast instead of interleaving its
    writes with the newer deploy's. Same-revision acquires are idempotent,
    so every shard Job of one deploy guards independently with no
    ordering requirement between them.
    """
    import datetime as datetime_mod
    import os
    import tempfile
    import time as time_mod

    if not str(revision).isdigit():
        raise click.ClickException(f"Revision must be numeric, got {revision!r}")
    os.makedirs(models_root, exist_ok=True)
    lock_path = os.path.join(models_root, "deploy.lock")

    def read_lock() -> str:
        try:
            with open(lock_path) as f:
                lock = json.load(f)
        except FileNotFoundError:
            return ""
        except ValueError:
            logger.warning("Corrupt deploy.lock at %s; overwriting", lock_path)
            return ""
        return str(lock.get("revision", "")) if isinstance(lock, dict) else ""

    def fail_stale(held: str) -> None:
        raise click.ClickException(
            f"A newer deploy (revision {held}) owns {models_root}; "
            f"this deploy (revision {revision}) is stale and must not write"
        )

    if check_only:
        held = read_lock()
        if held.isdigit() and int(held) > int(revision):
            fail_stale(held)
        click.echo(f"Lock check ok for revision {revision} (held: {held or 'none'})")
        return

    # The read-check-replace must not race a concurrent deploy (both could
    # pass the check, then the OLDER one could land its lock last). The
    # guard is a directory that is NEVER empty — acquirers stage
    # ``<unique>/held`` and atomically rename it onto the mutex path —
    # because POSIX rename replaces an EMPTY directory target silently
    # but fails (ENOTEMPTY) on a non-empty one. That one property makes
    # both acquisition (can't steal a live guard) and stale-break
    # restoration (can't clobber a successor's guard) atomic; a crashed
    # holder's stale guard is broken after a timeout (the critical
    # section below is milliseconds long).
    mutex = os.path.join(models_root, ".deploy.guard")

    def _unique(suffix: str) -> str:
        return f"{mutex}.{suffix}-{os.getpid()}-{time_mod.monotonic_ns()}"

    def _remove_guard(path: str) -> None:
        for entry in ("held", ""):
            try:
                os.rmdir(os.path.join(path, entry) if entry else path)
            except OSError:
                pass

    def _try_acquire() -> bool:
        staging = _unique("acquire")
        os.mkdir(staging)
        os.mkdir(os.path.join(staging, "held"))
        try:
            # Fails while ANY guard (always non-empty) sits at the path.
            os.rename(staging, mutex)
            return True
        except OSError:
            _remove_guard(staging)
            return False

    deadline = time_mod.monotonic() + 60
    while not _try_acquire():
        if time_mod.monotonic() > deadline:
            raise click.ClickException(
                f"Could not acquire {mutex} within 60s; if no other "
                "deploy is running, remove the stale directory"
            )
        try:
            age = time_mod.time() - os.stat(mutex).st_mtime
            if age > 300:
                # Break the stale guard via an atomic rename to a unique
                # name: exactly one waiter's rename succeeds, and only
                # that winner may dispose of the condemned dir. The
                # rename may still have caught a guard that was
                # broken-and-reacquired between our stat and our rename
                # (a sub-millisecond window), so the winner re-checks the
                # age of what it actually took: a young guard is handed
                # straight back — and because guards are non-empty, that
                # restore can never overwrite a successor's live guard
                # (rename fails ENOTEMPTY and we release ours instead;
                # a guard stands at the path either way).
                condemned = _unique("stale")
                try:
                    os.rename(mutex, condemned)
                except OSError:
                    pass  # another waiter already broke it
                else:
                    try:
                        renamed_age = (
                            time_mod.time() - os.stat(condemned).st_mtime
                        )
                    except OSError:
                        renamed_age = None
                    if renamed_age is not None and renamed_age <= 300:
                        try:
                            os.rename(condemned, mutex)
                        except OSError:
                            _remove_guard(condemned)
                    else:
                        logger.warning("Broke stale deploy mutex %s", mutex)
                        _remove_guard(condemned)
                continue
        except OSError:
            pass
        time_mod.sleep(0.5)
    try:
        held = read_lock()
        if held.isdigit() and int(held) > int(revision):
            fail_stale(held)
        fd, tmp = tempfile.mkstemp(dir=models_root, prefix=".deploy.lock.")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(
                    {
                        "revision": str(revision),
                        "acquired_at": datetime_mod.datetime.now(
                            datetime_mod.timezone.utc
                        ).isoformat(),
                    },
                    f,
                )
            os.replace(tmp, lock_path)  # atomic on the shared volume
        except OSError:
            try:
                os.unlink(tmp)
            finally:
                raise
    finally:
        _remove_guard(mutex)
    click.echo(f"Acquired deploy lock for revision {revision}")


@click.command("cleanup-revisions")
@click.argument("models-root", envvar="MODELS_ROOT")
@click.argument("current-revision", envvar="PROJECT_REVISION")
@click.option(
    "--keep",
    default=3,
    type=int,
    help="How many newest revisions to retain (the current one always is)",
)
@click.option("--dry-run", is_flag=True)
def cleanup_revisions(models_root: str, current_revision: str, keep: int, dry_run: bool):
    """
    Delete old model revisions under MODELS_ROOT, keeping the newest
    ``--keep`` plus always the current one.

    The reference cleans stale revisions in its workflow's onExit handler
    by deleting per-revision k8s resources (argo-workflow.yml.template
    onExit section); here revisions are directories on the shared model
    volume, so lifecycle is a filesystem sweep — no k8s API, no RBAC.
    """
    import os
    import shutil

    try:
        entries = sorted(
            (
                entry
                for entry in os.listdir(models_root)
                if os.path.isdir(os.path.join(models_root, entry)) and entry.isdigit()
            ),
            key=int,  # numeric, not lexicographic: '1000' is newer than '999'
        )
    except FileNotFoundError:
        raise click.ClickException(f"No such models root: {models_root}")

    retained = set(entries[-keep:] if keep > 0 else [])
    retained.add(current_revision)
    doomed = [entry for entry in entries if entry not in retained]
    failed = []
    for revision in doomed:
        path = os.path.join(models_root, revision)
        if dry_run:
            click.echo(f"Would delete {path}")
            continue
        logger.info("Deleting old revision %s", path)
        try:
            shutil.rmtree(path)
        except OSError as exc:
            # Surface it: a cleanup Job that silently leaves revisions
            # behind lets the shared volume fill — fail so k8s retries/alerts.
            logger.error("Could not delete %s: %s", path, exc)
            failed.append(revision)
    click.echo(
        f"Revisions: {len(entries) - len(doomed)} kept, "
        f"{len(doomed) - len(failed)} deleted"
        f"{' (dry run)' if dry_run else ''}"
    )
    if failed:
        raise click.ClickException(
            f"Failed to delete {len(failed)} revision(s): {', '.join(failed)}"
        )


@click.group("lifecycle")
def lifecycle_cli():
    """Self-healing fleet lifecycle: drift-triggered incremental
    rebuilds, canary promotion with auto-rollback, zero-downtime
    hot-swap (gordo_tpu.lifecycle; docs/lifecycle.md)."""


def _lifecycle_supervisor(
    collection_dir: str,
    machines_config: Optional[str],
    canary_fraction: Optional[float],
    auto_promote: Optional[bool] = None,
):
    from ..lifecycle import LifecycleConfig, LifecycleSupervisor

    machines = (
        _load_fleet_machines(machines_config) if machines_config else []
    )
    config = LifecycleConfig.from_env()
    if canary_fraction is not None:
        config.canary_fraction = canary_fraction
    if auto_promote is not None:
        config.auto_promote = auto_promote
    return LifecycleSupervisor(machines, collection_dir, config=config)


def _lifecycle_frames(machines) -> dict:
    """One probe window per machine: the machine's own dataset fetch
    (the scoring loop's data plane). Per-machine isolation — a machine
    whose provider is down simply contributes no probe rows this
    cycle."""
    from ..dataset import GordoBaseDataset

    frames = {}
    for machine in machines:
        try:
            dataset = (
                machine.dataset
                if isinstance(machine.dataset, GordoBaseDataset)
                else GordoBaseDataset.from_dict(machine.dataset)
            )
            X, _y = dataset.get_data()
            frames[machine.name] = X
        except Exception as exc:  # noqa: BLE001 - per-machine isolation
            logger.warning("lifecycle probe fetch failed for %s: %r",
                           machine.name, exc)
    return frames


def _echo_cycle(report) -> None:
    click.echo(f"phase: {report.phase}")
    if report.drifted:
        for name, reasons in sorted(report.drifted.items()):
            click.echo(f"  drifted {name}: {'; '.join(reasons)}")
    if report.canary_revision:
        click.echo(f"  canary revision: {report.canary_revision}")
    if report.gate is not None:
        verdict = "PASSED" if report.gate["passed"] else "FAILED"
        click.echo(f"  gates: {verdict}")
        for failure in report.gate["failures"]:
            click.echo(f"    {failure}")
    if report.promoted:
        click.echo(
            f"  promoted (swap {report.details.get('swap_seconds', 0)}s)"
        )
    if report.rolled_back:
        click.echo("  rolled back; serving stays on the last-good revision")


@lifecycle_cli.command("run")
@click.argument("machines-config", envvar="MACHINES_CONFIG")
@click.argument("collection-dir", envvar="MODEL_COLLECTION_DIR")
@click.option(
    "--once", is_flag=True, help="Run a single cycle and exit (cron mode)."
)
@click.option(
    "--interval",
    default=300.0,
    type=click.FloatRange(min=0.0),
    show_default=True,
    help="Seconds between cycles in loop mode.",
)
@click.option(
    "--cycles",
    default=None,
    type=click.IntRange(min=1),
    help="Stop after this many cycles (default: run forever).",
)
@click.option(
    "--canary-fraction",
    default=None,
    type=click.FloatRange(0.0, 1.0, min_open=True),
    help="Traffic slice routed to a canary under evaluation "
    "[GORDO_TPU_CANARY_FRACTION, default 0.25].",
)
@click.option(
    "--auto-promote/--no-auto-promote",
    default=True,
    show_default=True,
    help="Promote automatically when the gates pass; off leaves the "
    "canary serving its slice until `lifecycle promote`.",
)
@click.option(
    "--dry-run",
    is_flag=True,
    help="Observe and report drift only; never rebuild or route.",
)
def lifecycle_run(
    machines_config: str,
    collection_dir: str,
    once: bool,
    interval: float,
    cycles: Optional[int],
    canary_fraction: Optional[float],
    auto_promote: bool,
    dry_run: bool,
):
    """
    Supervise COLLECTION_DIR (a served revision directory): each cycle
    scores every machine's current data through the serving fleet,
    updates per-machine drift statistics, incrementally rebuilds
    members that tripped, canaries the result and promotes (or rolls
    back) through the gates. Crash-safe: state and build journals
    under ``<models root>/.lifecycle`` make every phase resumable.

    Canary/hot-swap ROUTING is per-process (the store is process
    memory): embed the supervisor in the serving process for live
    traffic splitting; a separately-running server picks promotions
    up at its next boot. See docs/lifecycle.md "Deployment model".
    """
    import time as time_mod

    supervisor = _lifecycle_supervisor(
        collection_dir, machines_config, canary_fraction, auto_promote
    )
    try:
        ran = 0
        while True:
            frames = _lifecycle_frames(supervisor.machines)
            if dry_run:
                supervisor.observe(frames)
                verdicts = supervisor.evaluate_drift()
                for name, verdict in sorted(verdicts.items()):
                    status = "DRIFTED" if verdict.drifted else "ok"
                    click.echo(
                        f"{name}: {status} {'; '.join(verdict.reasons)}"
                    )
            else:
                _echo_cycle(supervisor.run_cycle(frames))
            ran += 1
            if once or (cycles is not None and ran >= cycles):
                break
            time_mod.sleep(interval)
    finally:
        supervisor.close()


@lifecycle_cli.command("status")
@click.argument("models-root", envvar="MODELS_ROOT")
@click.option("--as-json", is_flag=True, help="Machine-readable output.")
def lifecycle_status(models_root: str, as_json: bool):
    """The lifecycle state and quarantine record for MODELS_ROOT (the
    directory holding the numbered revision dirs)."""
    from ..lifecycle import LifecycleState

    state = LifecycleState.load(models_root)
    quarantined = state.quarantined()
    if as_json:
        click.echo(
            json.dumps(
                {"state": state.doc, "quarantined": quarantined},
                indent=1,
                sort_keys=True,
                default=str,
            )
        )
        return
    click.echo(f"phase:    {state.phase}")
    click.echo(f"anchor:   {state.anchor_revision}")
    click.echo(f"serving:  {state.serving_revision}")
    click.echo(f"canary:   {state.canary_revision or '-'}")
    if state.stale:
        click.echo(f"stale:    {', '.join(state.stale)}")
    for entry in (state.doc.get("history") or [])[-5:]:
        click.echo(
            f"  {entry.get('event')}: serving={entry.get('serving_revision')}"
            f" canary={entry.get('canary_revision')}"
        )
    click.echo(f"quarantined canaries: {len(quarantined)}")
    for record in quarantined[-3:]:
        click.echo(
            f"  revision {record.get('canary_revision')}: "
            f"{'; '.join(record.get('reasons', [])[:2])}"
        )


@lifecycle_cli.command("promote")
@click.argument("collection-dir", envvar="MODEL_COLLECTION_DIR")
@click.option(
    "--machines-config",
    envvar="MACHINES_CONFIG",
    default=None,
    help="Machine YAML for fetching a probe window (gates need scored "
    "data; without it only --force can promote).",
)
@click.option(
    "--force",
    is_flag=True,
    help="Skip the gates (operator has verified the canary externally).",
)
def lifecycle_promote(
    collection_dir: str, machines_config: Optional[str], force: bool
):
    """Promote the current canary revision into serving."""
    supervisor = _lifecycle_supervisor(collection_dir, machines_config, None)
    try:
        if machines_config and not force:
            supervisor.observe(_lifecycle_frames(supervisor.machines))
        report = supervisor.promote(force=force)
    except RuntimeError as exc:
        raise click.ClickException(str(exc)) from exc
    finally:
        supervisor.close()
    _echo_cycle(report)
    if report.rolled_back:
        raise click.ClickException("gates failed; canary rolled back")


@lifecycle_cli.command("rollback")
@click.argument("collection-dir", envvar="MODEL_COLLECTION_DIR")
@click.option(
    "--reason",
    default="operator rollback",
    show_default=True,
    help="Recorded in the quarantine entry.",
)
def lifecycle_rollback(collection_dir: str, reason: str):
    """Roll back the current canary: drop its traffic slice, quarantine
    it, and keep serving the last-good revision."""
    supervisor = _lifecycle_supervisor(collection_dir, None, None)
    try:
        report = supervisor.rollback(reason)
    except RuntimeError as exc:
        raise click.ClickException(str(exc)) from exc
    finally:
        supervisor.close()
    _echo_cycle(report)


@click.group("perfmodel")
def perfmodel_cli():
    """The learned performance model: fit device-cost regressors from
    telemetry traces, inspect the promoted table, and evaluate learned
    vs analytic accuracy on a corpus."""


@perfmodel_cli.command("fit")
@click.argument("corpus-dir", type=click.Path(exists=True, file_okay=False))
@click.option(
    "--table",
    "table_path",
    default=None,
    type=click.Path(dir_okay=False, writable=True),
    help="The cost_table.json to promote into (default: "
    "GORDO_TPU_PERFMODEL_TABLE, else cost_table.json beside the corpus).",
)
@click.option(
    "--min-samples",
    default=None,
    type=int,
    help="Smallest (target, program) population to fit (default: "
    "GORDO_TPU_PERFMODEL_MIN_SAMPLES).",
)
@click.option(
    "--force",
    is_flag=True,
    help="Install the fit even when it loses the holdout accuracy gate "
    "(the sample floor still applies).",
)
@click.option("--as-json", "as_json", is_flag=True, help="Raw report JSON")
def perfmodel_fit(
    corpus_dir: str,
    table_path: Optional[str],
    min_samples: Optional[int],
    force: bool,
    as_json: bool,
):
    """Harvest CORPUS_DIR's traces (build + serve, rotated generations
    and worker variants merged), fit the per-program regressors, and
    promote them into the cost table IF each beats the analytic model
    and the incumbent on its holdout."""
    from ..perfmodel import fit_and_promote

    report = fit_and_promote(
        corpus_dir,
        table_path=table_path,
        min_samples=min_samples,
        force=force,
    )
    if as_json:
        click.echo(json.dumps(report, indent=1, sort_keys=True))
        return
    corpus = report.get("corpus") or {}
    click.echo(
        f"corpus: {corpus.get('rows', 0)} training row(s) from "
        f"{corpus.get('spans', 0)} span(s) in {corpus_dir}"
    )
    for entry in report.get("models") or []:
        inc = entry.get("incumbent_mae_log")
        click.echo(
            f"  {entry['target']}/{entry['program']}: n={entry['n']} "
            f"holdout={entry['holdout_mae_log']:.4f} "
            f"analytic={entry.get('analytic_mae_log')} "
            f"incumbent={inc if inc is not None else '-'} "
            f"-> {entry['reason']}"
        )
    click.echo(
        f"{'PROMOTED' if report.get('promoted') else 'not promoted'}: "
        f"{report.get('reason')}"
        + (f" ({report.get('table')})" if report.get("promoted") else "")
    )
    if not report.get("promoted") and not (report.get("models") or []):
        # an empty/thin corpus is normal at cold start — say so plainly
        click.echo("the analytic model remains the active fallback")


@perfmodel_cli.command("status")
@click.option(
    "--table",
    "table_path",
    default=None,
    type=click.Path(dir_okay=False),
    help="The cost table to inspect (default: GORDO_TPU_PERFMODEL_TABLE).",
)
@click.option("--as-json", "as_json", is_flag=True, help="Raw status JSON")
def perfmodel_status(table_path: Optional[str], as_json: bool):
    """What the cost table currently carries: calibration factors,
    fitted learned models and their holdout accuracy, corpus identity."""
    from ..perfmodel import default_table_path, section_status

    path = table_path or default_table_path()
    doc = section_status(path)
    if as_json:
        click.echo(json.dumps(doc, indent=1, sort_keys=True))
        return
    click.echo(f"table: {path or '(none; analytic defaults)'}")
    click.echo(
        f"calibrated: {doc['calibrated']}  learned: {doc['learned']}"
    )
    corpus = doc.get("corpus")
    if corpus:
        click.echo(
            f"corpus: {corpus.get('rows')} row(s), "
            f"fingerprint {corpus.get('fingerprint')}"
        )
    for entry in doc["models"]:
        click.echo(
            f"  {entry['target']}/{entry['program']}: n={entry['n']} "
            f"holdout_mae_log={entry['holdout_mae_log']}"
        )
    if not doc["models"]:
        click.echo("no learned models; predictions are analytic")


@perfmodel_cli.command("eval")
@click.argument("corpus-dir", type=click.Path(exists=True, file_okay=False))
@click.option(
    "--table",
    "table_path",
    default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="Evaluate THIS table's learned models (default: "
    "GORDO_TPU_PERFMODEL_TABLE, else cost_table.json beside the corpus).",
)
@click.option("--as-json", "as_json", is_flag=True, help="Raw report JSON")
def perfmodel_eval(
    corpus_dir: str, table_path: Optional[str], as_json: bool
):
    """Score a table's learned models against CORPUS_DIR's measured
    spans — learned vs analytic mean absolute log error per (target,
    program), without fitting or writing anything."""
    from ..perfmodel import default_table_path, harvest_corpus
    from ..perfmodel.model import analytic_prediction, evaluate_rows
    from ..planner.costmodel import load_table_safe

    path = table_path or default_table_path(corpus_dir)
    table = load_table_safe(path)
    rows, stats = harvest_corpus(corpus_dir)
    populations: dict = {}
    for row in rows:
        populations.setdefault((row.target, row.program), []).append(row)
    report = {
        "table": path,
        "corpus": stats,
        "models": [],
    }
    for (target, program), population in sorted(populations.items()):
        learned_mae, learned_n = evaluate_rows(
            population,
            lambda r: table.learned_predict(target, program, r.features),
        )
        analytic_mae, analytic_n = evaluate_rows(
            population,
            lambda r: analytic_prediction(table, target, program, r.features),
        )
        report["models"].append(
            {
                "target": target,
                "program": program,
                "rows": len(population),
                "learned_mae_log": round(learned_mae, 6)
                if learned_n
                else None,
                "learned_scored": learned_n,
                "analytic_mae_log": round(analytic_mae, 6)
                if analytic_n
                else None,
            }
        )
    if as_json:
        click.echo(json.dumps(report, indent=1, sort_keys=True))
        return
    click.echo(
        f"corpus: {len(rows)} row(s); table: "
        f"{path or '(analytic defaults)'}"
    )
    for entry in report["models"]:
        learned = entry["learned_mae_log"]
        click.echo(
            f"  {entry['target']}/{entry['program']}: rows={entry['rows']} "
            f"learned={learned if learned is not None else '-'} "
            f"(scored {entry['learned_scored']}) "
            f"analytic={entry['analytic_mae_log']}"
        )
    if not report["models"]:
        click.echo("no training rows in the corpus")


gordo_tpu_cli.add_command(workflow_cli)
gordo_tpu_cli.add_command(client_cli)
gordo_tpu_cli.add_command(build)
gordo_tpu_cli.add_command(build_fleet)
gordo_tpu_cli.add_command(plan_fleet)
gordo_tpu_cli.add_command(build_status)
gordo_tpu_cli.add_command(fleet_status)
gordo_tpu_cli.add_command(trace)
gordo_tpu_cli.add_command(slo_cli)
gordo_tpu_cli.add_command(lint)
gordo_tpu_cli.add_command(lockgraph)
gordo_tpu_cli.add_command(run_server_cli)
gordo_tpu_cli.add_command(wait_for_models)
gordo_tpu_cli.add_command(score)
gordo_tpu_cli.add_command(ensure_single_workflow)
gordo_tpu_cli.add_command(cleanup_revisions)
gordo_tpu_cli.add_command(lifecycle_cli)
gordo_tpu_cli.add_command(perfmodel_cli)


if __name__ == "__main__":
    gordo_tpu_cli()
