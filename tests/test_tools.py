"""Cluster tooling: pure command builders + hostfile generation (C15).

The reference's EC2 tool was untestable without AWS credentials; these
builders are pure functions, so the gcloud surface is verified offline.
"""

import json
import os

import pytest

from tools.tpu_pod import (
    TpuPodConfig,
    bootstrap_commands,
    create_cmd,
    delete_cmd,
    describe_cmd,
    endpoints_from_describe,
    hostfile_lines,
    kill_python_command,
    main,
    scp_cmd,
    ssh_cmd,
    train_command,
    write_hostfiles,
)

CFG = TpuPodConfig(name="p0", project="proj", zone="us-central2-b",
                   accelerator_type="v4-32")


class TestCommandBuilders:
    def test_create(self):
        cmd = create_cmd(CFG)
        assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "create"]
        assert "p0" in cmd and "v4-32" in cmd and "--project" in cmd
        assert "--spot" not in cmd
        spot = create_cmd(TpuPodConfig(name="p0", spot=True))
        assert "--spot" in spot

    def test_delete_quiet(self):
        assert "--quiet" in delete_cmd(CFG)

    def test_ssh_fan_out_all_workers(self):
        cmd = ssh_cmd(CFG, "echo hi")
        i = cmd.index("--worker")
        assert cmd[i + 1] == "all"
        assert cmd[cmd.index("--command") + 1] == "echo hi"

    def test_scp_recurse(self):
        cmd = scp_cmd(CFG, "./repo", "~/repo")
        assert "p0:~/repo" in cmd and "--recurse" in cmd

    def test_bootstrap_clones_and_builds_native(self):
        cmds = bootstrap_commands(CFG, "https://example.com/r.git", "v1")
        joined = " && ".join(cmds)
        assert "git clone" in joined and "--branch v1" in joined
        assert "make -C native" in joined

    def test_train_command_same_module_everywhere(self):
        c = train_command(CFG, ["--network", "ResNet18", "--batch-size", "1024"])
        assert "python3 -m pytorch_distributed_nn_tpu train" in c
        assert "--network ResNet18" in c
        assert "mpirun" not in c  # no MPI, no rank branching

    def test_train_command_gcs_checkpoint_sync(self):
        cfg = TpuPodConfig(name="p0", gcs_bucket="bkt")
        c = train_command(cfg, ["--network", "LeNet"])
        assert "gs://bkt/p0/checkpoints" in c and "gsutil" in c

    def test_train_command_periodic_sync_during_training(self):
        # the evaluator polls the bucket DURING the run; a post-exit-only
        # rsync would leave it blind (reference NFS dir was visible live)
        cfg = TpuPodConfig(name="p0", gcs_bucket="bkt")
        c = train_command(cfg, ["--network", "LeNet"], sync_interval=30)
        assert "while true; do sleep 30" in c
        assert c.count("gsutil") == 2  # periodic loop + final sync
        assert c.rstrip().endswith("exit $RC; }")  # training rc propagates
        # the '&' must be scoped inside the brace group, or it backgrounds
        # the whole cd/mkdir and-list and training runs from the wrong cwd
        assert "&& { (while" in c
        import subprocess
        import tempfile

        with tempfile.NamedTemporaryFile(mode="r") as f:
            probe = (
                c.replace("gsutil -m -q rsync -r", "true")
                .replace("python3 -m pytorch_distributed_nn_tpu train "
                         "--network LeNet --train-dir /tmp/p0-ckpt",
                         f"pwd > {f.name}")
                .replace("cd ~/pytorch_distributed_nn_tpu", "cd /tmp")
            )
            subprocess.run(["bash", "-c", probe], timeout=10,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
            assert f.read().strip() == "/tmp"

    def test_kill_python(self):
        assert "pkill" in kill_python_command()


class TestHostfiles:
    DESC = {
        "state": "READY",
        "networkEndpoints": [
            {"ipAddress": "10.0.0.2",
             "accessConfig": {"externalIp": "34.1.2.3"}},
            {"ipAddress": "10.0.0.3",
             "accessConfig": {"externalIp": "34.1.2.4"}},
        ],
    }

    def test_endpoints(self):
        eps = endpoints_from_describe(self.DESC)
        assert [e["ip"] for e in eps] == ["10.0.0.2", "10.0.0.3"]
        assert eps[0]["external_ip"] == "34.1.2.3"

    def test_hostfile_lines_reference_format(self):
        hosts, alias, addr = hostfile_lines(endpoints_from_describe(self.DESC))
        # format parity: tools/pytorch_ec2.py:689 '{ip}\tdeeplearning-worker{n}'
        assert hosts[0] == "10.0.0.2\tdeeplearning-worker1"
        assert alias == ["deeplearning-worker1", "deeplearning-worker2"]
        assert addr == ["10.0.0.2", "10.0.0.3"]

    def test_write_hostfiles(self, tmp_path):
        write_hostfiles(endpoints_from_describe(self.DESC), str(tmp_path))
        for f in ("hosts", "hosts_alias", "hosts_address"):
            assert (tmp_path / f).exists()
        assert (tmp_path / "hosts_address").read_text().strip() == \
            "10.0.0.2\n10.0.0.3"


class TestCliDryRun:
    def test_create_dry_run(self, capsys):
        rc = main(["create", "--name", "x", "--type", "v4-8", "--dry-run"])
        assert rc == 0
        err = capsys.readouterr().err
        assert "tpu-vm create x" in err.replace("'", "")

    def test_train_dry_run(self, capsys):
        rc = main(["train", "--name", "x", "--dry-run", "--",
                   "--network", "ResNet18"])
        assert rc == 0
        err = capsys.readouterr().err
        assert "pytorch_distributed_nn_tpu train" in err

    def test_ssh_requires_command(self):
        with pytest.raises(SystemExit):
            main(["ssh", "--name", "x", "--dry-run"])


FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "fixtures")
#: traces of this program from the TPU v5e (and one made by hand in the
#: same grammar), read-only here: tests/benchmark/ owns them
TRACES = ("hand", "resnet18_ckpt_save_v5e", "bert_base_step_v5e",
          "bert_base_dp4_step_v5e")


@pytest.mark.parametrize("name", TRACES)
class TestXplaneSummary:
    """The package's trace reader (the operator's per-op table) on what
    the profiler writes, against the benchmark's reader of the same file:
    two readers, independent on purpose, that must agree on every chip's
    ``XLA Ops`` time."""

    @staticmethod
    def _path(name):
        return os.path.join(FIXTURES, name + ".textproto")

    @pytest.mark.parametrize("collapse", [False, True])
    def test_rows_sum_to_each_chips_xla_ops(self, name, collapse):
        from benchmark import trace
        from pytorch_distributed_nn_tpu.utils import profiling

        summary = profiling.summarize_xplane(
            self._path(name), top=10 ** 6, collapse=collapse)
        chips = trace.load(self._path(name)).chips
        assert sorted(summary) == sorted(chips)
        for plane, lines in chips.items():
            ops = lines[trace.OPS_LINE]
            rows = summary[plane]
            assert sum(r.total_ms for r in rows) == pytest.approx(
                sum(e.end - e.start for e in ops) / 1e6)
            assert sum(r.count for r in rows) == len(ops)
            assert sum(r.pct for r in rows) == pytest.approx(100.0)
            # everything shown: no tail row
            assert all(not r.name.startswith("(other") for r in rows)
            if not collapse:
                assert {r.name for r in rows} == {e.text for e in ops}

    def test_top_folds_the_rest_into_one_tail_row(self, name):
        """Truncation must not drop device time: a table cut to ``top``
        rows still sums to the chip's total."""
        from pytorch_distributed_nn_tpu.utils import profiling

        full = profiling.summarize_xplane(
            self._path(name), top=10 ** 6, collapse=False)
        cut = profiling.summarize_xplane(
            self._path(name), top=3, collapse=False)
        assert sorted(cut) == sorted(full)
        for plane, rows in cut.items():
            assert len(full[plane]) > 3
            assert len(rows) == 4  # 3 shown + the folded tail
            assert rows[:3] == full[plane][:3]
            assert rows[-1].name == f"(other {len(full[plane]) - 3} ops)"
            assert rows[-1].count == sum(r.count for r in full[plane][3:])
            assert sum(r.total_ms for r in rows) == pytest.approx(
                sum(r.total_ms for r in full[plane]))
            assert sum(r.pct for r in rows) == pytest.approx(100.0)
