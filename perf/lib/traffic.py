"""Seeded traffic. A mix is a data file of parameters; these generators read
it. The program sees only what they generate.

Every seed gets the SAME sizes in the SAME order on the SAME client, sent
after the SAME pause: the seed decides the token ids and the sampling seeds,
nothing else. A
closed-loop window holds only some tens of long requests, and which of them
fall into it, and in which order the first prompts are prefilled, decides
its tail; a seed that shuffled the order, or only dealt the lists to other
clients, changed the work, and runs of different seeds differed far more
than two runs of one seed (PERF.md, Findings).
"""
import math

import numpy as np


def size_grid(spec, n):
    """``n`` whole sizes laid evenly over [lo, hi], on a linear or a
    logarithmic scale, both ends included. A bare number is that size."""
    if isinstance(spec, (int, float)):
        return [int(spec)] * n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if n == 1:
        return [int(round(math.sqrt(lo * hi) if spec.get("scale") == "log"
                          else (lo + hi) / 2))]
    out = []
    for i in range(n):
        f = i / (n - 1)
        v = (lo * (hi / lo) ** f if spec.get("scale") == "log"
             else lo + (hi - lo) * f)
        out.append(int(round(v)))
    return out


def _stride_permutation(n):
    """A fixed permutation of range(n) that scatters neighbours: steps of
    the whole number nearest n/golden-ratio that is coprime to n."""
    step = max(1, int(round(n * 0.6180339887)))
    while math.gcd(step, n) != 1:
        step += 1
    return [(i * step) % n for i in range(n)]


def session_multiset(params):
    """The fixed multiset of sessions, before any seed: each a prefix
    length and its requests' (suffix length, answer length, greedy)."""
    n = int(params["sessions"])
    per = int(params.get("requests_per_session", 1))
    prefix = (size_grid(params["prefix_len"], n)
              if params.get("prefix_len") else [0] * n)
    suffix = size_grid(params["suffix_len"], n * per)
    answer = size_grid(params["answer_len"], n * per)
    # pair long prompts with long and short answers alike
    answer = [answer[j] for j in _stride_permutation(n * per)]
    every = int(params.get("greedy_every", 0))
    temperature = float(params.get("temperature", 0.0))
    sessions = []
    # the sessions' own order is scattered too, so that a client's first
    # few sessions span the range whatever the seed deals it
    for slot, i in enumerate(_stride_permutation(n)):
        reqs = []
        for r in range(per):
            j = i * per + r
            greedy = temperature <= 0.0 or (every > 0 and j % every == 0)
            reqs.append({"suffix_len": suffix[j], "answer_len": answer[j],
                         "temperature": 0.0 if greedy else temperature,
                         "topk_first": (int(params.get("greedy_topk_first",
                                                       0)) if greedy else 0)})
        sessions.append({"prefix_len": prefix[i], "requests": reqs,
                         "index": slot})
    return sessions


def closed_loop_sessions(params, vocab, seed):
    """Per client, the sessions it runs one after another. A session is a
    shared prefix (possibly empty) and requests that each send the prefix
    plus a fresh suffix. Token ids are uniform over the vocabulary."""
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed), 0x7261])))
    sessions = session_multiset(params)
    n_clients = int(params["clients"])
    lists = [[] for _ in range(n_clients)]
    think_ms = float(params.get("think_ms", 0.0))
    stagger_ms = float(params.get("think_stagger_ms", 0.0))
    for k, s in enumerate(sessions):
        prefix = rng.integers(0, vocab, size=s["prefix_len"], dtype=np.int64)
        reqs = []
        for r in s["requests"]:
            suffix = rng.integers(0, vocab, size=r["suffix_len"],
                                  dtype=np.int64)
            reqs.append({
                "prompt": np.concatenate([prefix, suffix]).astype(np.int32),
                "prefix_len": int(s["prefix_len"]),
                "max_new": int(r["answer_len"]),
                "temperature": float(r["temperature"]),
                "topk_first": int(r["topk_first"]),
                "seed": int(rng.integers(0, 2 ** 31 - 1)),
                # the client's own pause before it sends this request: a
                # fixed turnaround plus a step per client, so that a
                # request never races the scheduler's admission point and
                # two clients freed by one step always queue in one order
                "think_s": (think_ms + stagger_ms * (k % n_clients)) / 1e3,
            })
        lists[k % n_clients].append(reqs)
    return lists


def resident_batch(params, image, class_dim, seed):
    """A device-resident synthetic batch whose rows all differ: images
    uniform in [0, 1), labels uniform over the classes. One jitted call."""
    import jax
    import jax.numpy as jnp

    batch = int(params["batch"])

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        img = jax.random.uniform(k1, (batch,) + tuple(image), jnp.float32)
        label = jax.random.randint(k2, (batch, 1), 0, class_dim, jnp.int32)
        return img, label

    return make(jax.random.key(int(seed) % (2 ** 63)))
