"""The example CLI trainers must run end-to-end (reference: the example/
scripts double as integration tests in the reference's CI)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=560):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script)] + list(args),
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, "%s failed:\n%s\n%s" % (
        script, proc.stdout[-3000:], proc.stderr[-3000:])
    return proc.stdout


@pytest.mark.slow
def test_train_mnist_cli():
    out = _run("train_mnist.py", "--num-epochs", "2",
               "--num-examples", "600", "--batch-size", "50")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_train_mnist_record_pipeline():
    """fit convergence gated through the real RecordIO image pipeline
    (VERDICT weak #10)."""
    out = _run("train_mnist.py", "--num-epochs", "2",
               "--num-examples", "600", "--batch-size", "50", "--use-rec")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_lstm_bucketing_cli():
    out = _run("lstm_bucketing.py")
    assert "final validation perplexity" in out


@pytest.mark.slow
def test_model_parallel_lstm_cli():
    out = _run("model_parallel_lstm.py")
    assert "ok: nll" in out


@pytest.mark.slow
def test_gluon_mnist_cli():
    out = _run("gluon_mnist.py", "--num-epochs", "2",
               "--num-examples", "800", "--hybridize")
    assert "final validation accuracy" in out


@pytest.mark.nightly
def test_gluon_image_classification_cli():
    """Model-zoo net + Trainer + hybridize (reference
    example/gluon/image_classification.py parity)."""
    out = _run("gluon_image_classification.py", "--num-epochs", "10")
    assert "final train accuracy" in out


@pytest.mark.nightly
def test_word_language_model_cli():
    out = _run("word_language_model.py", "--num-epochs", "6")
    assert "final validation perplexity" in out


@pytest.mark.nightly
def test_train_ssd_cli():
    """SSD detection convergence gate (SURVEY §2.15 example/ssd parity):
    multi-scale heads + MultiBox ops must learn to localize."""
    out = _run("train_ssd.py", "--num-epochs", "35",
               "--num-examples", "256", "--batch-size", "32")
    assert "mean IoU" in out


@pytest.mark.nightly
def test_train_rcnn_cli():
    """Fast R-CNN-style ROI pipeline (reference example/rcnn parity):
    ROIPooling + an in-graph CustomOp proposal-target must learn."""
    out = _run("train_rcnn.py", "--num-epochs", "25",
               "--num-examples", "128")
    assert "final ROI classification accuracy" in out


@pytest.mark.slow
def test_benchmark_score_cli():
    """Inference perf-table script (reference benchmark_score.py parity)."""
    out = _run("benchmark_score.py", "--network", "lenet",
               "--batch-sizes", "4", "--iters", "3")
    assert "img/s" in out


@pytest.mark.slow
def test_fine_tune_cli():
    """Checkpoint -> new head -> frozen-backbone fine-tune (reference
    fine-tune.py parity: set_params(allow_missing) + fixed_param_names)."""
    out = _run("fine_tune.py")
    assert "fine-tuned" in out


@pytest.mark.nightly
def test_dcgan_cli():
    """Adversarial two-Trainer training (reference example/gluon/dcgan.py
    parity): D margin must grow, G statistics must move toward the data."""
    out = _run("dcgan.py", "--num-epochs", "4")
    assert "generated mean" in out


@pytest.mark.nightly
def test_train_cifar10_cli():
    """Color RecordIO + crop/mirror augmentation through the fit harness
    (reference train_cifar10.py parity, small-image resnet)."""
    out = _run("train_cifar10.py", "--num-epochs", "6",
               "--num-examples", "1200")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_pipeline_moe_transformer_cli():
    """Pipeline stages + MoE through the PipelineModule user surface
    (VERDICT r3 #4): perplexity must fall on the cyclic corpus."""
    out = _run("pipeline_moe_transformer.py", "--stages", "2",
               "--experts", "4", "--num-epochs", "2", "--num-batches",
               "10", "--d-model", "32", "--seq-len", "16")
    assert "final-ppl=" in out


@pytest.mark.slow
def test_pipeline_transformer_1f1b_hetero_cli():
    """1F1B schedule + unequal per-stage FFN widths (heterogeneous
    pipeline, VERDICT r4 #3) through the same CLI."""
    out = _run("pipeline_moe_transformer.py", "--stages", "2",
               "--experts", "0", "--schedule", "1f1b",
               "--ffn-widths", "128,64", "--num-epochs", "2",
               "--num-batches", "10", "--d-model", "32",
               "--seq-len", "16")
    assert "final-ppl=" in out


@pytest.mark.slow
def test_super_resolution_cli():
    """ESPCN-style sub-pixel upscaling (reference
    example/gluon/super_resolution.py parity): PSNR must beat nearest."""
    out = _run("super_resolution.py", "--num-epochs", "14",
               "--num-examples", "60")
    assert "PSNR" in out


@pytest.mark.nightly
def test_actor_critic_cli():
    """Actor-critic RL (reference example/gluon/actor_critic.py parity):
    mean episode length must grow 1.5x over training."""
    out = _run("actor_critic.py", "--num-episodes", "120")
    assert "mean episode length" in out


@pytest.mark.slow
def test_cnn_text_classification_cli():
    """Kim-CNN over parallel conv widths + max-over-time pooling
    (reference example/cnn_text_classification parity)."""
    out = _run("cnn_text_classification.py", "--num-epochs", "5",
               "--num-examples", "900")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_autoencoder_cli():
    """Greedy layer-wise pretrain + fine-tune stacked AE (reference
    example/autoencoder parity)."""
    out = _run("autoencoder.py", "--num-epochs", "8",
               "--pretrain-epochs", "3", "--num-examples", "1000")
    assert "val mse" in out


@pytest.mark.slow
def test_bi_lstm_sort_cli():
    """BidirectionalCell LSTM learns to sort (reference
    example/bi-lstm-sort parity)."""
    out = _run("bi_lstm_sort.py", "--num-epochs", "6",
               "--num-examples", "900")
    assert "per-position sort accuracy" in out


@pytest.mark.slow
def test_lstm_crf_cli():
    """BiLSTM-CRF: dynamic-programming loss (forward algorithm) +
    Viterbi decode; the transition matrix must learn the tag grammar."""
    out = _run("lstm_crf.py", "--num-epochs", "6", "--num-examples",
               "200")
    assert "tag accuracy" in out


@pytest.mark.slow
def test_neural_style_cli():
    """Gradient-wrt-input optimization (Gatys-style): Gram statistics
    must move to the style target while content survives."""
    out = _run("neural_style.py", "--num-steps", "120")
    assert "style loss" in out


@pytest.mark.nightly
@pytest.mark.slow
def test_dqn_cli():
    """DQN: replay buffer + frozen target network + epsilon decay on
    cart-pole; greedy eval must beat random by >2.5x."""
    out = _run("dqn.py", "--num-episodes", "80")
    assert "greedy eval" in out


@pytest.mark.nightly
@pytest.mark.slow
def test_tree_lstm_cli():
    """Child-sum Tree-LSTM: recursive composition over expression trees
    with topology-bucketed batching; must beat the bag-of-leaves
    baseline decisively."""
    out = _run("tree_lstm.py")
    assert "eval accuracy" in out


@pytest.mark.slow
def test_train_imagenet_benchmark_cli():
    """The BASELINE north-star CLI (reference train_imagenet.py flag
    surface) in synthetic --benchmark mode: must train to memorization
    on the fixed synthetic batch."""
    out = _run("train_imagenet.py", "--network", "resnet",
               "--num-layers", "18", "--benchmark", "1",
               "--num-classes", "10", "--image-shape", "3,64,64",
               "--num-epochs", "3", "--batch-size", "32",
               "--num-examples", "256", "--lr", "0.05",
               "--lr-step-epochs", "")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_train_imagenet_recordio_cli(tmp_path):
    """The same CLI over a real RecordIO file (the reference's data
    path): pack synthetic images with the recordio codec, train, and
    assert the accuracy line prints."""
    import numpy as np
    import cv2
    sys.path.insert(0, os.path.join(ROOT))
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    n, size = 192, 64
    y = rng.randint(0, 4, n)
    x = rng.rand(n, size, size, 3).astype(np.float32) * 0.2
    for c in range(4):
        x[y == c, :, :, c % 3] += 0.6
    for split, idx in (("train", slice(0, 160)), ("val", slice(160, n))):
        rec = recordio.MXRecordIO(str(tmp_path / (split + ".rec")), "w")
        xs, ys = x[idx], y[idx]
        for i in range(xs.shape[0]):
            ok, enc = cv2.imencode(
                ".png", (xs[i][:, :, ::-1] * 255).astype(np.uint8))
            rec.write(recordio.pack(
                recordio.IRHeader(0, float(ys[i]), i, 0), enc.tobytes()))
        rec.close()
    out = _run("train_imagenet.py", "--network", "resnet",
               "--num-layers", "18",
               "--data-train", str(tmp_path / "train.rec"),
               "--data-val", str(tmp_path / "val.rec"),
               "--image-shape", "3,56,56", "--num-classes", "4",
               "--num-epochs", "2", "--batch-size", "32",
               "--num-examples", "160", "--lr", "0.05",
               "--lr-step-epochs", "", "--rgb-mean", "0,0,0")
    assert "final validation accuracy" in out


@pytest.mark.slow
def test_adversary_fgsm_cli():
    """FGSM attack (reference example/adversary): gradient wrt input of
    a TRAINED model collapses its accuracy within an Linf budget."""
    out = _run("adversary_fgsm.py")
    assert "FGSM" in out


@pytest.mark.slow
def test_ctc_ocr_cli():
    """CTC over unsegmented digit strips (reference example/ctc +
    warpctc): alignment-free sequence learning + greedy decode."""
    out = _run("ctc_ocr.py")
    assert "sequence accuracy" in out


@pytest.mark.slow
def test_svm_mnist_cli():
    """SVMOutput margin heads (reference example/svm_mnist): both SVM
    variants and softmax clear the bar on the same features."""
    out = _run("svm_mnist.py")
    assert "l2-svm" in out


@pytest.mark.slow
def test_multi_task_cli():
    """Two loss heads on one backbone with two bound labels (reference
    example/multi-task); must beat split-budget single-task models."""
    out = _run("multi_task.py")
    assert "multi-task" in out
