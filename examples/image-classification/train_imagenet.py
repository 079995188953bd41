#!/usr/bin/env python
"""ImageNet-class training (ResNet/Inception/VGG/AlexNet) with Module.fit.

Analogue of the reference's example/image-classification/train_imagenet.py
(the script behind BASELINE.md's training tables). Feeds ImageRecordIter
when a RecordIO file is given (--data-train), else synthetic device-side
data at full speed. bf16 compute is on by default (MXNET_COMPUTE_DTYPE).

    python examples/image-classification/train_imagenet.py \
        --network resnet-50 --batch-size 32 --num-batches 100
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def _make_synth_rec(path, n, shape, num_classes, quality=80):
    """Pack n random JPEGs at the training resolution into a .rec."""
    import cv2
    import numpy as np
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = rng.randint(0, 255, (shape[1], shape[2], 3), np.uint8)
        ok, enc = cv2.imencode(".jpg", img,
                               [int(cv2.IMWRITE_JPEG_QUALITY), quality])
        assert ok
        w.write(recordio.pack(recordio.IRHeader(0, float(i % num_classes),
                                                i, 0), enc.tobytes()))
    w.close()
    return path


def run_io_benchmark(args, shape, dev):
    """Training WITH the input pipeline in the measured loop. Reports:
    feed-only (iterator steady state), compute-only (device-resident
    batch), and with-IO (fit_step over live iterator batches) — overlap
    means with-IO tracks max(feed, compute), not their sum (the engine-
    style compute/IO pipelining of SURVEY §3.1 recreated with async
    dispatch + native prefetch threads)."""
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models

    rec = args.data_train
    if rec is None:
        # enough records that the timed window never wraps (a wrap pays a
        # full prefetcher teardown/rebuild inside the measurement)
        n_rec = max(args.io_records, (args.io_steps + 8) * args.batch_size)
        rec = os.path.join(tempfile.mkdtemp(), "synth_imagenet.rec")
        print("packing %d synthetic records at %s ..." % (n_rec, str(shape)))
        _make_synth_rec(rec, n_rec, shape, args.num_classes)

    def make_iter():
        cls = (mx.io.ImageRecordUInt8Iter if args.uint8
               else mx.io.ImageRecordIter)
        return cls(
            path_imgrec=rec, data_shape=shape, batch_size=args.batch_size,
            shuffle=True, rand_mirror=True, preprocess_threads=4,
            prefetch_buffer=4)

    sym = models.get_symbol(args.network, num_classes=args.num_classes)
    mod = mx.mod.Module(sym, context=dev)
    it = make_iter()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9, "wd": 1e-4})

    def sync():
        outs = mod.get_outputs()
        np.asarray(outs[0].asnumpy().reshape(-1)[0])

    def steps_over(source, n_steps, batches=None):
        done = 0
        while done < n_steps:
            if batches is not None:
                batch = batches[done % len(batches)]
            else:
                try:
                    batch = source.next()
                except StopIteration:
                    source.reset()
                    batch = source.next()
            mod.fit_step(batch)
            done += 1
        sync()


    n = args.io_steps
    # warmup: compile + fill prefetch
    first = it.next()
    resident = mx.io.DataBatch(
        [mx.nd.array(d.asnumpy().astype("float32")) for d in first.data],
        [l.copy() for l in first.label])
    steps_over(None, 3, batches=[resident])

    # compute-only: device-resident batch
    t0 = time.time()
    steps_over(None, n, batches=[resident])
    t_compute = (time.time() - t0) / n

    # feed-only: iterator steady state (fresh iterator, no training)
    feed_it = make_iter()
    feed_it.next()  # spin up decode threads
    t0 = time.time()
    got = 0
    while got < n:
        try:
            feed_it.next()
        except StopIteration:
            feed_it.reset()
            continue
        got += 1
    t_feed = (time.time() - t0) / n

    # h2d-only: host->device placement of a fresh batch
    import jax as _jax

    host_batch = first.data[0].asnumpy()
    if args.uint8:
        host_batch = host_batch.astype("uint8")
    jdev = dev.jax_device()
    x = _jax.device_put(host_batch, jdev); x.block_until_ready()
    t0 = time.time()
    for _ in range(max(3, n // 3)):
        x = _jax.device_put(host_batch, jdev)
        x.block_until_ready()
    t_h2d = (time.time() - t0) / max(3, n // 3)

    # with IO: training loop fed by the live iterator through the
    # device prefetcher (decode + H2D overlap the device step)
    it.reset()
    dev_it = mx.io.DevicePrefetchIter(it, ctx=dev, depth=3,
                                      cast_dtype="float32" if args.uint8
                                      else None)
    steps_over(dev_it, 3)  # fill the device-side double buffer
    t0 = time.time()
    steps_over(dev_it, n)
    t_step = (time.time() - t0) / n
    dev_it.close()

    t_max = max(t_feed, t_h2d, t_compute)
    t_sum = t_feed + t_h2d + t_compute
    overlap = ("OVERLAPPED" if t_step < 0.75 * t_sum or t_step <= 1.2 * t_max
               else "NOT overlapped")
    print("io-bench %s bs%d: feed %.1f ms  h2d %.1f ms  compute %.1f ms  "
          "with-IO %.1f ms (max %.1f, sum %.1f) -> %s; %.1f img/s with IO"
          % (args.network, args.batch_size, t_feed * 1e3, t_h2d * 1e3,
             t_compute * 1e3, t_step * 1e3, t_max * 1e3, t_sum * 1e3,
             overlap, args.batch_size / t_step))


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet-50")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--num-batches", type=int, default=100,
                   help="batches per epoch for synthetic data")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--data-train", default=None, help=".rec file")
    p.add_argument("--model-prefix", default=None)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--benchmark-io", action="store_true",
                   help="measure the training loop WITH the record input "
                        "pipeline: reports feed-only, compute-only and "
                        "with-IO step times (overlap = with-IO tracking "
                        "max, not sum — reference perf.md:149-155 measures "
                        "training through train_imagenet + iterator)")
    p.add_argument("--io-steps", type=int, default=30)
    p.add_argument("--io-records", type=int, default=512)
    p.add_argument("--uint8", action="store_true",
                   help="uint8 wire format (ImageRecordUInt8Iter) + "
                        "on-device cast: 4x less H2D traffic")
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models

    if args.dtype != "float32":
        os.environ.setdefault("MXNET_COMPUTE_DTYPE", args.dtype)

    shape = tuple(int(x) for x in args.image_shape.split(","))
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())

    if args.benchmark_io:
        run_io_benchmark(args, shape, dev)
        return

    if args.data_train:
        train = mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=shape,
            batch_size=args.batch_size, shuffle=True, rand_mirror=True)
    else:
        rng = np.random.RandomState(0)
        n = args.batch_size * args.num_batches
        X = rng.uniform(-1, 1, (n,) + shape).astype(np.float32)
        y = rng.randint(0, args.num_classes, n).astype(np.float32)
        train = mx.io.NDArrayIter(X, y, batch_size=args.batch_size,
                                  label_name="softmax_label")

    sym = models.get_symbol(args.network, num_classes=args.num_classes)
    # distributed runs: non-zero ranks checkpoint under prefix-<rank>
    # (reference example/image-classification/common/fit.py:29-43)
    rank = int(os.environ.get("MXNET_TPU_WORKER_RANK",
                              os.environ.get("MXNET_TPU_PROC_ID", "0")))
    if args.model_prefix and rank > 0:
        args.model_prefix = "%s-%d" % (args.model_prefix, rank)
    mod = mx.mod.Module(sym, context=dev)
    tic = time.time()
    mod.fit(train, num_epoch=args.num_epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2.0),
            batch_end_callback=[mx.callback.Speedometer(args.batch_size, 20)],
            epoch_end_callback=([mx.callback.do_checkpoint(args.model_prefix)]
                                if args.model_prefix else None),
            kvstore=None)
    dur = time.time() - tic
    total = args.num_epochs * args.num_batches * args.batch_size
    print("trained %d images in %.1fs (%.1f img/s incl. compile)"
          % (total, dur, total / dur))


if __name__ == "__main__":
    main()
