"""chip_smoke.py's phases on the CPU at a tiny size (conftest's 8
virtual devices stand in for chips), and its refusal to run — or to
print ``ok`` — without a TPU."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import bench
import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 512


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("smoke") / "corpus.libsvm")
    bench.ensure_data(path, 1)  # one 4000-row block, ~2 MB
    return path


@pytest.fixture(scope="module")
def ingested(corpus):
    return chip_smoke.phase_ingest(corpus, jax.devices()[0], rows=ROWS,
                                   nnz_bucket=ROWS * 45)


def test_ingest_hbm_matches_host_parse(ingested):
    batches, host, report = ingested
    assert report["assembly_path"] == "native-padded"
    assert report["rows"] == 4000 and report["batches"] == 8
    assert report["hbm_hash"] == report["host_hash"]
    assert report["hbm_bytes"] == sum(
        a.nbytes for b in batches for a in b.values())
    assert len(host) == len(batches)


def test_golden_parity_on_a_part(corpus):
    out = chip_smoke.phase_golden_parity(corpus, prefix_bytes=256 << 10)
    assert out["part"] == f"0/{os.path.getsize(corpus) // (256 << 10)}"
    assert out["bytes"] >= 256 << 10 and out["rows"] > 0


def test_consumer_matches_float64_replay(ingested):
    batches, host, _ = ingested
    out = chip_smoke.phase_consumer(batches, host, steps=20)
    assert out["steps"] == 20 and out["num_features"] == 2 ** 20
    for k in ("loss_abs", "w_abs", "b_abs"):
        assert out[f"{k}_err"] <= out[f"{k}_bound"]


@pytest.mark.parametrize("fault", ["learning_rate", "frozen_bias"])
def test_reference_catches_a_wrong_step(ingested, fault):
    """The check has teeth: the model's steps do not pass for a replay
    with a 10% larger learning rate, nor with a bias left at its init."""
    batches, host, _ = ingested
    from dmlc_tpu.models import SparseLinearModel
    model = SparseLinearModel(2 ** 20)
    params = model.init_params()
    losses = []
    for b in batches[:4]:
        params, loss = model.train_step(params, b)
        losses.append(float(loss))
    eps = chip_smoke.SOFTPLUS_EPS
    bounds = (eps, eps, 4 * 0.1 * eps)
    ref = chip_smoke.reference_sgd(host, 4, 2 ** 20, 0.1)
    chip_smoke.check_steps(losses, params, *ref, *bounds)
    if fault == "learning_rate":
        ref = chip_smoke.reference_sgd(host, 4, 2 ** 20, 0.11)
        match = "(loss_abs|w_abs) error"
    else:
        params = {**params, "b": model.init_params()["b"]}
        match = "b_abs error"
    with pytest.raises(AssertionError, match=match):
        chip_smoke.check_steps(losses, params, *ref, *bounds)


@pytest.mark.parametrize("fault", [None, "frozen_bias"])
def test_sharded_path_on_four_devices(corpus, monkeypatch, fault):
    """The sharded steps match one chip; with the bias held at its init
    in the sharded step they do not."""
    if fault == "frozen_bias":
        from dmlc_tpu.models import SparseLinearModel
        real = SparseLinearModel.make_sharded_train_step

        def frozen(self, mesh, axis="data"):
            step = real(self, mesh, axis)

            def held(p, batch):
                new, loss = step(p, batch)
                return {**new, "b": p["b"]}, loss
            return held

        monkeypatch.setattr(SparseLinearModel, "make_sharded_train_step",
                            frozen)
        with pytest.raises(AssertionError, match="error .* over"):
            chip_smoke.phase_sharded(corpus, jax.devices()[:4], steps=6,
                                     rows=256, nnz_bucket=256 * 45)
        return
    out = chip_smoke.phase_sharded(corpus, jax.devices()[:4], steps=6,
                                   rows=256, nnz_bucket=256 * 45)
    assert out["chips"] == 4 and len(out["shards_on"]) == 4
    assert out["stream_hash"] == out["one_chip_hash"]
    assert out["rows"] == 4000
    assert out["b_ref_abs"] > out["b_abs_bound"]


def test_stream_hash_ignores_batching_but_not_content(ingested):
    _, host, report = ingested
    merged = {k: np.concatenate([np.asarray(b[k])[:int(b["num_rows"])]
                                 for b in host])
              for k in ("label", "weight")}
    lens = np.concatenate([np.diff(np.asarray(b["offset"], np.int64))
                           [:int(b["num_rows"])] for b in host])
    cols = {k: np.concatenate([np.asarray(b[k])[:int(b["num_nnz"])]
                               for b in host]) for k in ("index", "value")}
    offset = np.concatenate([[0], np.cumsum(lens)])
    whole = chip_smoke.StreamHash()
    whole.add(merged["label"], merged["weight"], offset, cols["index"],
              cols["value"])
    assert whole.hexdigest() == report["hbm_hash"]
    cols["value"][7] += 1.0
    changed = chip_smoke.StreamHash()
    changed.add(merged["label"], merged["weight"], offset, cols["index"],
                cols["value"])
    assert changed.hexdigest() != report["hbm_hash"]


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_cpu_run_is_refused(script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""
