"""CLI front end: python -m veles_tpu workflow.py config.py root.k=v
(reference: veles/__main__.py contract)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, cwd=REPO, timeout=300):
    env = dict(os.environ)
    return subprocess.run(
        [sys.executable, "-m", "veles_tpu"] + args,
        capture_output=True, text=True, cwd=cwd, env=env,
        timeout=timeout)


@pytest.fixture
def workflow_file(tmp_path):
    p = tmp_path / "wf.py"
    p.write_text(textwrap.dedent("""
        import json
        from veles_tpu.config import root
        from veles_tpu.models import mnist

        def run(launcher):
            launcher.create_workflow(
                mnist.create_workflow,
                loader={"minibatch_size": 25,
                        "n_train": int(root.test.n_train),
                        "n_valid": 50},
                decision={"max_epochs": 2})
            launcher.initialize()
            launcher.run()
            d = launcher.workflow.decision
            tr = [h["loss"] for h in d.history if h["class"] == "train"]
            print("RESULT " + json.dumps({
                "train_losses": tr,
                "epochs": launcher.workflow.loader.epoch_number}))
    """))
    return str(p)


@pytest.fixture
def config_file(tmp_path):
    p = tmp_path / "cfg.py"
    p.write_text("root.test.n_train = 100\n")
    return str(p)


class TestCLI:
    def test_workflow_with_config_and_override(self, workflow_file,
                                               config_file):
        r = run_cli([workflow_file, config_file, "root.test.n_train=150",
                     "-b", "cpu"])
        assert r.returncode == 0, r.stderr[-2000:]
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT ")][0]
        data = json.loads(line[len("RESULT "):])
        assert data["epochs"] == 2
        assert data["train_losses"][-1] < data["train_losses"][0]

    def test_numpy_backend_flag(self, workflow_file, config_file):
        r = run_cli([workflow_file, config_file, "-b", "numpy"])
        assert r.returncode == 0, r.stderr[-2000:]

    def test_log_events_jsonl_sink(self, workflow_file, config_file,
                                   tmp_path):
        """--log-events FILE appends every run event as one JSON line
        (the reference's MongoDB event-sink parity, file-shaped)."""
        events = tmp_path / "events.jsonl"
        r = run_cli([workflow_file, config_file, "-b", "numpy",
                     "--log-events", str(events)])
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [json.loads(ln) for ln in
                 events.read_text().splitlines()]
        assert lines, "no events recorded"
        assert all({"ts", "level", "unit", "message"} <= set(e)
                   for e in lines)
        # the run's lifecycle is in the durable record
        assert any("epoch" in e["message"].lower() or
                   "workflow" in e["unit"].lower() for e in lines)

    def test_dump_config(self, workflow_file, config_file):
        r = run_cli([workflow_file, config_file, "--dump-config"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "n_train = 100" in r.stdout

    def test_bad_workflow_file(self, tmp_path):
        p = tmp_path / "empty.py"
        p.write_text("x = 1\n")
        r = run_cli([str(p)])
        assert r.returncode == 2
        assert "defines neither" in r.stderr


class TestEnsembleCli:
    def test_ensemble_train_then_test(self, tmp_path):
        """--ensemble-train N persists members; --ensemble-test
        aggregates them (reference CLI ensemble surface)."""
        ens = str(tmp_path / "ens.npz")
        r = run_cli(["veles_tpu/models/mnist.py", "-b", "cpu",
                     "--ensemble-train", "2", "--ensemble-test",
                     "--ensemble-file", ens,
                     "root.mnist.loader.minibatch_size=25",
                     "root.mnist.loader.n_train=500",
                     "root.mnist.loader.n_valid=100",
                     "root.mnist.decision.max_epochs=5"])
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["members"] == 2
        assert len(out["member_valid_errors_pct"]) == 2
        # mean-probability aggregation has no worst-member guarantee
        # in general — assert the number is a sane percentage and the
        # members actually trained (not chance-level 90% on 10 classes)
        assert 0.0 <= out["ensemble_valid_error_pct"] <= 100.0
        assert max(out["member_valid_errors_pct"]) < 60.0
        assert os.path.exists(ens)

    def test_ensemble_needs_create_workflow(self, tmp_path):
        p = tmp_path / "wf.py"
        p.write_text("def run(launcher):\n    pass\n")
        r = run_cli([str(p), "--ensemble-train", "2", "-b", "numpy"])
        assert r.returncode == 2
        assert "create_workflow" in r.stderr

    def test_ensemble_edge_cases(self, tmp_path):
        p = tmp_path / "wf.py"
        p.write_text("def create_workflow(launcher):\n    pass\n")
        # N < 1 rejected cleanly
        r = run_cli([str(p), "--ensemble-train", "0", "-b", "numpy"])
        assert r.returncode == 2 and "N >= 1" in r.stderr
        # test-only with no member file: clean message, not traceback
        r = run_cli([str(p), "--ensemble-test", "-b", "numpy",
                     "--ensemble-file", str(tmp_path / "none.npz")])
        assert r.returncode == 2
        assert "does not exist" in r.stderr
        assert "Traceback" not in r.stderr


class TestServeModelsCli:
    """The --serve-models entry on the smoke-tested CLI surface (the
    full subprocess round trip lives in tests/test_serve.py, which
    drives this same entry through serve.client.HiveClient)."""

    def test_bad_model_spec_is_usage_error(self):
        r = run_cli(["--serve-models", "not-a-pair"])
        assert r.returncode == 2
        assert "NAME=PACKAGE" in r.stderr
        assert "Traceback" not in r.stderr

    def test_missing_package_is_usage_error(self, tmp_path):
        r = run_cli(["--serve-models",
                     f"m={tmp_path}/nope.vpkg"])
        assert r.returncode == 2
        assert "no such package" in r.stderr


class TestServeFleetCli:
    """The --serve-fleet entry on the smoke-tested CLI surface (the
    full 2-replica protocol round trip lives in tests/test_fleet.py
    TestFleetCliProtocol)."""

    def test_bad_model_spec_is_usage_error(self, tmp_path):
        r = run_cli(["--serve-fleet", "2", "not-a-pair"])
        assert r.returncode == 2
        assert "NAME=PACKAGE" in r.stderr
        assert "Traceback" not in r.stderr

    def test_missing_package_is_usage_error(self, tmp_path):
        r = run_cli(["--serve-fleet", "2",
                     f"m={tmp_path}/nope.vpkg"])
        assert r.returncode == 2
        assert "no such package" in r.stderr

    def test_zero_replicas_is_usage_error(self, tmp_path):
        pkg = tmp_path / "m.vpkg"
        pkg.write_bytes(b"x")
        r = run_cli(["--serve-fleet", "0", f"m={pkg}"])
        assert r.returncode == 2
        assert ">= 1" in r.stderr

    def test_bad_canary_spec_is_usage_error(self, tmp_path):
        pkg = tmp_path / "m.vpkg"
        pkg.write_bytes(b"x")
        # a canary naming an unregistered model must die at parse
        # time, before any replica spawns
        r = run_cli(["--serve-fleet", "1", f"m={pkg}",
                     "--canary", "ghost=m:0.5"])
        assert r.returncode == 2
        assert "ghost" in r.stderr
        assert "Traceback" not in r.stderr
