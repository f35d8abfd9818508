"""Each cell run end to end on the CPU at a tiny size: set-up, window and
check through the same traffic files and metric readers as on the chip;
and no result line off the accelerator."""
import pytest

from bench import harness, rehearsal


@pytest.mark.parametrize("cell", sorted(rehearsal.TINY))
def test_cell_rehearsal_is_correct(cell):
    out = rehearsal.run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.resolve(cell)["end_to_end"]}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_traced_rehearsal_reads_per_layer_metrics():
    out = rehearsal.run("ftn_overlay.replan", trace=True)
    assert out["correct"], out["checks"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: only the host-side readings exist
    assert "admit_host_ms.replan" in out["metrics"]
    assert "kernel_ms.sweep" not in out["metrics"]


def test_no_result_line_off_the_accelerator(capsys):
    rc = harness.main(["--workload", "ftn_overlay.replan", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
