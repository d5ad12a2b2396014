"""The rest of the reference's fluid benchmark suite on one TPU chip
(reference benchmark/fluid/: mnist.py, vgg.py, stacked_dynamic_lstm.py —
resnet is bench.py's north star and machine_translation is
transformer_bench.py). One JSON line per workload:
  {"workload": ..., "value": imgs_or_words_per_sec, "unit": ...,
   "step_ms": ..., "loss_first"/"loss_last", ...}

Workload definitions mirror the reference scripts' defaults:
  - mnist: LeNet-style conv_pool x2 + fc, bs 128 (mnist.py:45 cnn_model)
  - vgg:   VGG-16 on cifar-shaped [3,32,32], bs 128, batch-norm conv
           groups (vgg.py:68 conv_block -> img_conv_group)
  - stacked_lstm: imdb-style classifier — embedding 512 -> fc tanh ->
    DynamicRNN custom LSTM cell (fc gates) -> last-step pool -> softmax,
    bs 32, crop 100 tokens (stacked_dynamic_lstm.py:97 main)

Env: SUITE_WORKLOADS=mnist,vgg,stacked_lstm  SUITE_ITERS  SUITE_WARMUP
     SUITE_ALLOW_CPU=1 (smoke/test mode: run tiny shapes on CPU and label
     backend honestly — never a perf claim)
"""
import json
import os
import sys

import numpy as np


def _bench_program(exe, scope, prog, feed, fetch, iters, warmup):
    # fetch-sync slope timing (benchmarks/_timing.py)
    from benchmarks._timing import step_time_from_iters

    losses = []
    a_param = prog.global_block().all_parameters()[0].name

    def _dispatch(_i):
        out = exe.run(prog, feed=feed, fetch_list=fetch, return_numpy=False)
        losses.append(out[0])
        return scope.find_var(a_param)

    per_step_s, _ev = step_time_from_iters(_dispatch, iters, warmup)
    # sample a few losses for integrity evidence; always includes first
    # and last
    from benchmarks._timing import sample_indices

    idx = sample_indices(len(losses), k=6)
    vals = [float(np.asarray(losses[i]).ravel()[0]) for i in idx]
    return per_step_s, vals


def _run_workload(name, quick):
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard

    rng = np.random.RandomState(0)
    main, startup, scope = Program(), Program(), fluid.Scope()
    main.random_seed = startup.random_seed = 7
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            if name == "mnist":
                bs = 8 if quick else 128
                img = layers.data(name="img", shape=[1, 28, 28],
                                  dtype="float32")
                label = layers.data(name="label", shape=[1], dtype="int64")
                # reference mnist.py cnn_model: 2x simple_img_conv_pool
                conv1 = fluid.nets.simple_img_conv_pool(
                    img, filter_size=5, num_filters=20, pool_size=2,
                    pool_stride=2, act="relu")
                conv2 = fluid.nets.simple_img_conv_pool(
                    conv1, filter_size=5, num_filters=50, pool_size=2,
                    pool_stride=2, act="relu")
                logit = layers.fc(input=conv2, size=10, act="softmax")
                cost = layers.mean(layers.cross_entropy(input=logit,
                                                        label=label))
                feed = {"img": jnp.asarray(
                            rng.rand(bs, 1, 28, 28).astype(np.float32)),
                        "label": jnp.asarray(rng.randint(
                            0, 10, (bs, 1)).astype(np.int64))}
                unit, per_step = "images/sec", bs
            elif name == "vgg":
                bs = 4 if quick else 128
                img = layers.data(name="img", shape=[3, 32, 32],
                                  dtype="float32")
                label = layers.data(name="label", shape=[1], dtype="int64")

                def conv_block(ipt, num_filter, groups, dropouts):
                    return fluid.nets.img_conv_group(
                        input=ipt, pool_size=2, pool_stride=2,
                        conv_num_filter=[num_filter] * groups,
                        conv_filter_size=3, conv_act="relu",
                        conv_with_batchnorm=True,
                        conv_batchnorm_drop_rate=dropouts,
                        pool_type="max")

                c1 = conv_block(img, 64, 2, [0.3, 0.0])
                c2 = conv_block(c1, 128, 2, [0.4, 0.0])
                c3 = conv_block(c2, 256, 3, [0.4, 0.4, 0.0])
                c4 = conv_block(c3, 512, 3, [0.4, 0.4, 0.0])
                c5 = conv_block(c4, 512, 3, [0.4, 0.4, 0.0])
                drop = layers.dropout(c5, dropout_prob=0.5)
                fc1 = layers.fc(input=drop, size=512, act=None)
                bn = layers.batch_norm(fc1, act="relu")
                drop2 = layers.dropout(bn, dropout_prob=0.5)
                fc2 = layers.fc(input=drop2, size=512, act=None)
                logit = layers.fc(input=fc2, size=10, act="softmax")
                cost = layers.mean(layers.cross_entropy(input=logit,
                                                        label=label))
                feed = {"img": jnp.asarray(
                            rng.rand(bs, 3, 32, 32).astype(np.float32)),
                        "label": jnp.asarray(rng.randint(
                            0, 10, (bs, 1)).astype(np.int64))}
                unit, per_step = "images/sec", bs
            else:  # stacked_lstm
                bs = 4 if quick else 32
                crop = 8 if quick else 100
                emb_dim, lstm_size, vocab = 512, 512, 5147
                if quick:
                    emb_dim = lstm_size = 32
                words = layers.data(name="words", shape=[1], dtype="int64",
                                    lod_level=1)
                label = layers.data(name="label", shape=[1], dtype="int64")
                sent = layers.embedding(words, size=[vocab, emb_dim])
                sent = layers.fc(input=sent, size=lstm_size, act="tanh",
                                 num_flatten_dims=2)
                rnn = layers.DynamicRNN()
                with rnn.block():
                    word = rnn.step_input(sent)
                    prev_h = rnn.memory(value=0.0, shape=[lstm_size])
                    prev_c = rnn.memory(value=0.0, shape=[lstm_size])

                    def gate(ipt, hidden):
                        g0 = layers.fc(input=ipt, size=lstm_size,
                                       bias_attr=True)
                        g1 = layers.fc(input=hidden, size=lstm_size,
                                       bias_attr=False)
                        return layers.sums(input=[g0, g1])

                    f = layers.sigmoid(gate(word, prev_h))
                    i = layers.sigmoid(gate(word, prev_h))
                    o = layers.sigmoid(gate(word, prev_h))
                    c_t = layers.tanh(gate(word, prev_h))
                    cell = layers.sums(input=[
                        layers.elementwise_mul(x=f, y=prev_c),
                        layers.elementwise_mul(x=i, y=c_t)])
                    hidden = layers.elementwise_mul(
                        x=o, y=layers.tanh(cell))
                    rnn.update_memory(prev_c, cell)
                    rnn.update_memory(prev_h, hidden)
                    rnn.output(hidden)
                last = layers.sequence_last_step(rnn())
                logit = layers.fc(input=last, size=2, act="softmax")
                cost = layers.mean(layers.cross_entropy(input=logit,
                                                        label=label))
                feed = {"words": jnp.asarray(rng.randint(
                            0, vocab, (bs, crop, 1)).astype(np.int64)),
                        "words@LEN": jnp.asarray(
                            np.full((bs,), crop, np.int32)),
                        "label": jnp.asarray(rng.randint(
                            0, 2, (bs, 1)).astype(np.int64))}
                unit, per_step = "words/sec", bs * crop
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
        exe = fluid.Executor()
        exe.run(startup)
        iters = int(os.environ.get("SUITE_ITERS", "3" if quick else "30"))
        warmup = int(os.environ.get("SUITE_WARMUP", "1" if quick else "5"))
        step_s, losses = _bench_program(exe, scope, main, feed, [cost],
                                        iters, warmup)
    import jax

    distinct = len({round(v, 6) for v in losses})
    return {
        "workload": name,
        "value": round(per_step / step_s, 2),
        "unit": unit,
        "backend": jax.default_backend(),
        "batch": per_step if unit == "words/sec" else feed["label"].shape[0],
        "step_ms": round(step_s * 1000, 3),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "distinct_losses": distinct,
        "finite": bool(np.isfinite(losses).all()),
        "quick_mode": quick,
    }


def main():
    allow_cpu = os.environ.get("SUITE_ALLOW_CPU") == "1"
    import jax
    if not allow_cpu:
        from benchmarks._timing import require_tpu

        require_tpu()
    quick = allow_cpu and jax.default_backend() != "tpu"
    rc = 0
    for name in os.environ.get(
            "SUITE_WORKLOADS", "mnist,vgg,stacked_lstm").split(","):
        try:
            print(json.dumps(_run_workload(name.strip(), quick)), flush=True)
        except Exception as e:
            print(json.dumps({"workload": name, "error": f"{type(e).__name__}: {e}"}))
            rc = 1
    return rc


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
