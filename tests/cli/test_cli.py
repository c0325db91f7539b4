"""CLI tests (reference model: tests/gordo/cli/)."""

import json
import os

import pytest
import yaml
from click.testing import CliRunner

from gordo_tpu import serializer
from gordo_tpu.cli import gordo_tpu_cli
from gordo_tpu.cli.cli import expand_model, get_all_score_strings

MACHINE_CONFIG = {
    "name": "test-machine",
    "project_name": "test-project",
    "dataset": {
        "type": "RandomDataset",
        "train_start_date": "2020-01-01T00:00:00+00:00",
        "train_end_date": "2020-01-05T00:00:00+00:00",
        "tag_list": ["tag-1", "tag-2"],
    },
    "model": {
        "gordo_tpu.models.JaxAutoEncoder": {
            "kind": "feedforward_model",
            "encoding_dim": [8, 4],
            "encoding_func": ["tanh", "tanh"],
            "decoding_dim": [4, 8],
            "decoding_func": ["tanh", "tanh"],
            "epochs": 1,
        }
    },
}


@pytest.fixture
def runner():
    return CliRunner()


def test_version(runner):
    result = runner.invoke(gordo_tpu_cli, ["--version"])
    assert result.exit_code == 0
    assert result.output.strip()


def test_build_via_env(runner, tmp_path):
    out_dir = tmp_path / "out"
    result = runner.invoke(
        gordo_tpu_cli,
        ["build"],
        env={
            "MACHINE": json.dumps(MACHINE_CONFIG),
            "OUTPUT_DIR": str(out_dir),
        },
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert (out_dir / "model.pkl").is_file()
    assert (out_dir / "metadata.json").is_file()
    metadata = serializer.load_metadata(str(out_dir))
    assert metadata["name"] == "test-machine"
    # Model config was round-tripped through the serializer and re-keyed by
    # the canonical module path with its construction params preserved
    model_def = metadata["model"]["gordo_tpu.models.estimators.JaxAutoEncoder"]
    assert model_def["kind"] == "feedforward_model"
    assert model_def["epochs"] == 1


def test_build_print_cv_scores(runner, tmp_path):
    result = runner.invoke(
        gordo_tpu_cli,
        ["build", "--print-cv-scores"],
        env={
            "MACHINE": json.dumps(MACHINE_CONFIG),
            "OUTPUT_DIR": str(tmp_path / "out"),
        },
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    assert "explained-variance-score_fold-mean=" in result.output


def test_build_model_parameter_expansion(runner, tmp_path):
    config = dict(MACHINE_CONFIG)
    config["model"] = (
        '{"gordo_tpu.models.JaxAutoEncoder": '
        '{"kind": "feedforward_hourglass", "epochs": {{ n_epochs }}}}'
    )
    result = runner.invoke(
        gordo_tpu_cli,
        ["build", "--model-parameter", "n_epochs,1"],
        env={"MACHINE": json.dumps(config), "OUTPUT_DIR": str(tmp_path / "out")},
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output


def test_build_exit_code_and_exception_report(runner, tmp_path):
    config = dict(MACHINE_CONFIG)
    # tz-naive dates → ConfigException → exit code 100
    config["dataset"] = dict(
        config["dataset"], train_start_date="2020-01-01", train_end_date="2020-01-05"
    )
    report_file = tmp_path / "exception.json"
    result = runner.invoke(
        gordo_tpu_cli,
        ["build", "--exceptions-report-level", "MESSAGE"],
        env={
            "MACHINE": json.dumps(config),
            "OUTPUT_DIR": str(tmp_path / "out"),
            "EXCEPTIONS_REPORTER_FILE": str(report_file),
        },
    )
    assert result.exit_code == 100
    report = json.loads(report_file.read_text())
    assert report["type"] == "ConfigException"
    assert "message" in report


def test_build_fleet(runner, tmp_path):
    machines_yaml = yaml.safe_dump(
        {
            "machines": [
                dict(MACHINE_CONFIG, name=f"fleet-m-{i}") for i in range(2)
            ]
        }
    )
    config_path = tmp_path / "machines.yaml"
    config_path.write_text(machines_yaml)
    out_dir = tmp_path / "out"
    result = runner.invoke(
        gordo_tpu_cli,
        ["build-fleet", str(config_path), str(out_dir)],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    for i in range(2):
        assert (out_dir / f"fleet-m-{i}" / "model.pkl").is_file()
        metadata = serializer.load_metadata(str(out_dir / f"fleet-m-{i}"))
        assert metadata["name"] == f"fleet-m-{i}"


def test_build_fleet_resume_skips_journaled_machines(runner, tmp_path):
    """`build-fleet --resume` must skip machines journaled complete (no
    rebuild: artifact bytes/mtime untouched) and rebuild any machine
    whose artifact is missing — the post-crash recovery contract."""
    import shutil

    machines_yaml = yaml.safe_dump(
        {
            "machines": [
                dict(MACHINE_CONFIG, name=f"resume-m-{i}") for i in range(2)
            ]
        }
    )
    config_path = tmp_path / "machines.yaml"
    config_path.write_text(machines_yaml)
    out_dir = tmp_path / "out"
    result = runner.invoke(
        gordo_tpu_cli,
        ["build-fleet", str(config_path), str(out_dir)],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert (out_dir / "build_state.json").is_file()
    kept = out_dir / "resume-m-0" / "model.pkl"
    kept_stat = (kept.read_bytes(), kept.stat().st_mtime_ns)
    # simulate a crash that lost one machine's artifact
    shutil.rmtree(out_dir / "resume-m-1")

    result = runner.invoke(
        gordo_tpu_cli,
        ["build-fleet", str(config_path), str(out_dir), "--resume"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert (out_dir / "resume-m-1" / "model.pkl").is_file()
    # the journaled-complete machine was not rebuilt
    assert (kept.read_bytes(), kept.stat().st_mtime_ns) == kept_stat


def test_build_fleet_register_cache(runner, tmp_path):
    machines_yaml = yaml.safe_dump(
        {"machines": [dict(MACHINE_CONFIG, name="cached-m")]}
    )
    config_path = tmp_path / "machines.yaml"
    config_path.write_text(machines_yaml)
    register = tmp_path / "register"

    def run(out):
        result = runner.invoke(
            gordo_tpu_cli,
            [
                "build-fleet",
                str(config_path),
                str(out),
                "--model-register-dir",
                str(register),
            ],
            catch_exceptions=False,
        )
        assert result.exit_code == 0, result.output

    run(tmp_path / "out1")
    first = serializer.load_metadata(str(tmp_path / "out1" / "cached-m"))
    assert (register / "builds").is_dir()

    run(tmp_path / "out2")
    second = serializer.load_metadata(str(tmp_path / "out2" / "cached-m"))
    # Second run was a cache hit: same trained artifact, retrieval stamped
    assert "date_of_retrieval" in second["metadata"]["user_defined"]
    assert (
        first["metadata"]["build_metadata"]["model"]["model_creation_date"]
        == second["metadata"]["build_metadata"]["model"]["model_creation_date"]
    )


def test_expand_model():
    expanded = expand_model(
        '{"pkg.Model": {"depth": {{ depth }}}}', {"depth": 3}
    )
    assert expanded == {"pkg.Model": {"depth": 3}}


def test_expand_model_missing_parameter():
    with pytest.raises(ValueError, match="Model parameter missing value"):
        expand_model('{"pkg.Model": {"depth": {{ depth }}}}', {})


def test_get_all_score_strings_format(runner, tmp_path):
    from gordo_tpu.builder import ModelBuilder
    from gordo_tpu.machine import Machine

    machine = Machine.from_config(MACHINE_CONFIG, project_name="test-project")
    _, machine_out = ModelBuilder(machine).build()
    scores = get_all_score_strings(machine_out)
    assert any(s.startswith("r2-score_fold-1=") for s in scores)


# -- revision lifecycle commands --------------------------------------------


def test_wait_for_models_returns_when_present(tmp_path):
    from gordo_tpu.cli.cli import wait_for_models

    for name in ("w-a", "w-b"):
        (tmp_path / name).mkdir()
        (tmp_path / name / "metadata.json").write_text("{}")
    result = CliRunner().invoke(
        wait_for_models,
        [str(tmp_path), "--name", "w-a", "--name", "w-b", "--timeout", "5"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    assert "All 2 models present" in result.output


def test_wait_for_models_times_out_naming_missing(tmp_path):
    from gordo_tpu.cli.cli import wait_for_models

    (tmp_path / "w-a").mkdir()
    (tmp_path / "w-a" / "metadata.json").write_text("{}")
    result = CliRunner().invoke(
        wait_for_models,
        [
            str(tmp_path),
            "--name", "w-a", "--name", "w-missing",
            "--timeout", "1", "--poll-interval", "1",
        ],
    )
    assert result.exit_code != 0
    assert "w-missing" in result.output


def test_wait_for_models_reads_expected_models_env(tmp_path, monkeypatch):
    from gordo_tpu.cli.cli import wait_for_models

    (tmp_path / "env-a").mkdir()
    (tmp_path / "env-a" / "metadata.json").write_text("{}")
    monkeypatch.setenv("EXPECTED_MODELS", '["env-a"]')
    result = CliRunner().invoke(
        wait_for_models, [str(tmp_path), "--timeout", "5"], catch_exceptions=False
    )
    assert result.exit_code == 0


def test_cleanup_revisions_keeps_newest_and_current(tmp_path):
    from gordo_tpu.cli.cli import cleanup_revisions

    # five numeric revision dirs + one non-revision dir that must survive
    for revision in ("100", "200", "300", "400", "500"):
        (tmp_path / revision).mkdir()
    (tmp_path / "register").mkdir()
    result = CliRunner().invoke(
        cleanup_revisions,
        [str(tmp_path), "200", "--keep", "2"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    kept = sorted(p.name for p in tmp_path.iterdir())
    # newest two (400, 500) + current (200) + non-revision dir
    assert kept == ["200", "400", "500", "register"]


def test_cleanup_revisions_dry_run(tmp_path):
    from gordo_tpu.cli.cli import cleanup_revisions

    for revision in ("100", "200"):
        (tmp_path / revision).mkdir()
    result = CliRunner().invoke(
        cleanup_revisions,
        [str(tmp_path), "200", "--keep", "1", "--dry-run"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    assert sorted(p.name for p in tmp_path.iterdir()) == ["100", "200"]
    assert "Would delete" in result.output


def test_build_fleet_partial_failure_exit_code_and_artifacts(runner, tmp_path):
    """failFast:false at the CLI: good machines' artifacts land, the exit
    code maps the first failure (InsufficientDataError -> 80), and the
    exception report is written for the k8s termination message."""
    config = {
        "machines": [
            {
                "name": "ok-machine",
                "project_name": "p",
                "model": {
                    "gordo_tpu.models.JaxAutoEncoder": {
                        "kind": "feedforward_hourglass",
                        "epochs": 1,
                    }
                },
                "dataset": {
                    "type": "RandomDataset",
                    "train_start_date": "2020-01-01T00:00:00+00:00",
                    "train_end_date": "2020-01-02T00:00:00+00:00",
                    "tag_list": ["bf-1", "bf-2"],
                },
            },
            {
                "name": "starved-machine",
                "project_name": "p",
                "model": {
                    "gordo_tpu.models.JaxAutoEncoder": {
                        "kind": "feedforward_hourglass",
                        "epochs": 1,
                    }
                },
                "dataset": {
                    "type": "RandomDataset",
                    "train_start_date": "2020-01-01T00:00:00+00:00",
                    "train_end_date": "2020-01-02T00:00:00+00:00",
                    "tag_list": ["bf-3", "bf-4"],
                    "n_samples_threshold": 10_000_000,
                },
            },
        ]
    }
    config_path = tmp_path / "machines.yaml"
    config_path.write_text(yaml.safe_dump(config))
    out_dir = tmp_path / "out"
    report_path = tmp_path / "termination-log"

    from gordo_tpu.cli.cli import build_fleet

    result = runner.invoke(
        build_fleet,
        [
            str(config_path),
            str(out_dir),
            "--exceptions-reporter-file",
            str(report_path),
            "--exceptions-report-level",
            "MESSAGE",
        ],
    )
    assert result.exit_code == 80  # InsufficientDataError's mapped code
    assert (out_dir / "ok-machine" / "model.pkl").exists()
    assert not (out_dir / "starved-machine").exists()
    report = json.loads(report_path.read_text())
    assert "InsufficientDataError" in report["type"]


def test_cleanup_revisions_orders_numerically(tmp_path):
    """'1000' is newer than '999' — retention must sort numerically."""
    from gordo_tpu.cli.cli import cleanup_revisions

    for revision in ("999", "1000"):
        (tmp_path / revision).mkdir()
    result = CliRunner().invoke(
        cleanup_revisions,
        [str(tmp_path), "1000", "--keep", "1"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    assert sorted(p.name for p in tmp_path.iterdir()) == ["1000"]


class TestEnsureSingleWorkflow:
    """The deploy-lock guard (reference ensure-single-workflow semantics,
    inverted: the stale deploy aborts itself)."""

    def _run(self, runner, root, revision, *extra):
        return runner.invoke(
            gordo_tpu_cli,
            ["ensure-single-workflow", str(root), revision, *extra],
        )

    def test_fresh_acquire_writes_lock(self, runner, tmp_path):
        result = self._run(runner, tmp_path, "1600000000000")
        assert result.exit_code == 0, result.output
        import json as json_mod

        lock = json_mod.load(open(tmp_path / "deploy.lock"))
        assert lock["revision"] == "1600000000000"

    def test_same_revision_is_idempotent(self, runner, tmp_path):
        assert self._run(runner, tmp_path, "1600000000000").exit_code == 0
        assert self._run(runner, tmp_path, "1600000000000").exit_code == 0

    def test_newer_revision_takes_over(self, runner, tmp_path):
        assert self._run(runner, tmp_path, "1600000000000").exit_code == 0
        assert self._run(runner, tmp_path, "1600000000001").exit_code == 0
        import json as json_mod

        lock = json_mod.load(open(tmp_path / "deploy.lock"))
        assert lock["revision"] == "1600000000001"

    def test_stale_revision_fails(self, runner, tmp_path):
        assert self._run(runner, tmp_path, "1600000000001").exit_code == 0
        result = self._run(runner, tmp_path, "1600000000000")
        assert result.exit_code != 0
        assert "stale" in result.output
        # and the newer lock is untouched
        import json as json_mod

        lock = json_mod.load(open(tmp_path / "deploy.lock"))
        assert lock["revision"] == "1600000000001"

    def test_check_only_does_not_write(self, runner, tmp_path):
        result = self._run(runner, tmp_path, "1600000000000", "--check-only")
        assert result.exit_code == 0, result.output
        assert not (tmp_path / "deploy.lock").exists()

    def test_check_only_stale_fails(self, runner, tmp_path):
        assert self._run(runner, tmp_path, "1600000000005").exit_code == 0
        result = self._run(runner, tmp_path, "1600000000004", "--check-only")
        assert result.exit_code != 0

    def test_corrupt_lock_is_overwritten(self, runner, tmp_path):
        (tmp_path / "deploy.lock").write_text("{not json")
        result = self._run(runner, tmp_path, "1600000000000")
        assert result.exit_code == 0, result.output

    def test_non_numeric_revision_rejected(self, runner, tmp_path):
        result = self._run(runner, tmp_path, "not-a-revision")
        assert result.exit_code != 0


def test_run_server_starts_one_serving_process_by_default(runner, monkeypatch):
    """Every worker process initialises the accelerator, and a chip
    belongs to one process at a time: with no ``--workers`` the server
    is one process."""
    from gordo_tpu.cli import cli

    calls = []
    monkeypatch.setattr(
        cli, "run_server", lambda host, port, workers, *a, **kw: calls.append(workers)
    )
    monkeypatch.delenv("GORDO_SERVER_WORKERS", raising=False)
    result = runner.invoke(gordo_tpu_cli, ["run-server", "--port", "5999"])
    assert result.exit_code == 0, result.output
    assert calls == [1]
