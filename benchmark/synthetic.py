"""Everything a run feeds the program, made from numbers in data files and the
run's seed: length draws, documents and their token ids, the id-level
tokenizer, and the token shards a training job reads.

Shapes and content are kept apart on purpose. Every seed gets the *same* set
of lengths and arrival gaps (drawn once from the ``shape_seed`` a traffic file
states) in another order, and other token ids: so the work of a run does not
depend on its seed, only which request meets which does.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Sequence

import numpy as np

MAX_SEED = 2 ** 63 - 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for (seed, stream...); any non-negative seed."""
    if not 0 <= int(seed) <= MAX_SEED:
        raise ValueError(f"seed {seed} out of range")
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def lognormal_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths, lognormal with the stated median and sigma, clipped
    to [min, max]."""
    raw = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), size=n)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(np.int64)


def zipf_ids(n: int, low: int, high: int, exponent: float,
             rng: np.random.Generator) -> np.ndarray:
    """n token ids in [low, high), id low+r with probability ~ 1/(r+1)^exponent."""
    ranks = np.arange(1, high - low + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(exponent))
    cdf /= cdf[-1]
    return (low + np.searchsorted(cdf, rng.random(n), side="right")).astype(np.int64)


class IdTokenizer:
    """A prompt is its token ids written in decimal, separated by spaces. The
    program tokenizes by splitting; nothing is learned and nothing is loaded.
    ``eos_id`` is the vocabulary size, an id no logit column has, so greedy
    decoding can never stop early and ``max_tokens`` fixes the output length.
    """

    def __init__(self, vocab_size: int, bos_id: int = 1):
        self.vocab_size = int(vocab_size)
        self.bos_id = int(bos_id)
        self.eos_id = int(vocab_size)
        self.pad_id = 0

    def tokenize(self, text: str) -> List[int]:
        return [int(t) for t in text.split()]

    def detokenize(self, ids: Sequence[int]) -> str:
        return " ".join(str(int(i)) for i in ids)


def prompt_text(ids: Sequence[int]) -> str:
    return " ".join(str(int(i)) for i in ids)


def serve_requests(mix: Dict[str, Any], vocab_size: int, seed: int) -> List[Dict[str, Any]]:
    """The mix's fixed set of (prompt length, output length) pairs in this
    seed's order, each with this seed's token ids. ``prompt_tokens`` counts the
    BOS the program prepends; ids avoid 0..2 (pad, BOS, a conventional EOS)."""
    n = int(mix["num_requests"])
    shape = rng_for(int(mix["shape_seed"]), 0)
    p_len = lognormal_lengths(mix["prompt_tokens"], n, shape)
    o_len = lognormal_lengths(mix["output_tokens"], n, shape)
    order = rng_for(seed, 1).permutation(n)
    ids_rng = rng_for(seed, 2)
    out = []
    for i in order:
        body = ids_rng.integers(3, vocab_size, size=int(p_len[i]) - 1)
        out.append({"prompt_ids": body.tolist(), "prompt_tokens": int(p_len[i]),
                    "max_tokens": int(o_len[i])})
    return out


def poisson_arrivals(mix: Dict[str, Any], seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the start of load) of an open loop at the
    mix's fixed rate over ramp + window: one fixed set of exponential gaps
    (``shape_seed``), in this seed's order, scaled to end inside the span."""
    span = float(mix.get("ramp_s", 0.0)) + float(seconds)
    n = max(1, int(round(float(mix["rate_per_s"]) * span)))
    gaps = rng_for(int(mix["shape_seed"]), 1).exponential(1.0, size=n)
    gaps = gaps[rng_for(seed, 3).permutation(n)]
    due = np.cumsum(gaps)
    return due * (span * (1.0 - 0.5 / n) / due[-1])


def write_token_shards(job: Dict[str, Any], vocab_size: int, seed: int,
                       out_dir: str, steps: int) -> Dict[str, Any]:
    """One shard of packed documents in the program's ``token_shards`` format
    (flat little-endian ids + index.json): the job's fixed set of document
    lengths in this seed's order, an EOS after each, Zipf ids from the seed.
    Holds ``steps`` batches of ``batch_size`` windows of seq_len + 1, plus the
    loader's validation tail."""
    docs = job["documents"]
    window = int(job["seq_len"]) + 1
    need = (int(steps) * int(job["batch_size"]) + 2) * window
    need = int(need / (1.0 - 0.02)) + window  # the loader keeps 1% for validation
    shape = rng_for(int(job["shape_seed"]), 0)
    n_docs = max(8, int(2 * need / float(docs["median"])))
    lens = lognormal_lengths(docs, n_docs, shape)
    lens = lens[rng_for(seed, 1).permutation(n_docs)]
    keep = int(np.searchsorted(np.cumsum(lens + 1), need)) + 1
    if keep > n_docs:
        raise ValueError("document set too small for the job; raise n_docs")
    lens = lens[:keep]
    eos = int(job["eos_id"])
    ids = zipf_ids(int(lens.sum()), eos + 1, vocab_size, float(docs["zipf_exponent"]),
                   rng_for(seed, 2))
    total = int(lens.sum()) + keep
    flat = np.empty(total, np.int64)
    ends = np.cumsum(lens + 1) - 1
    is_eos = np.zeros(total, bool)
    is_eos[ends] = True
    flat[is_eos] = eos
    flat[~is_eos] = ids
    flat = flat[:need]
    dtype = np.uint16 if vocab_size <= 0xFFFF else np.uint32
    os.makedirs(out_dir, exist_ok=True)
    flat.astype(dtype).tofile(os.path.join(out_dir, "shard_00000.bin"))
    index = {"dtype": np.dtype(dtype).name, "shard_tokens": int(need),
             "total_tokens": int(need), "files": ["shard_00000.bin"],
             "vocab_size": int(vocab_size), "eos_id": eos}
    with open(os.path.join(out_dir, "index.json"), "w") as f:
        json.dump(index, f)
    return {"tokens": int(need), "documents": int(keep),
            "doc_len_median": float(np.median(lens)), "doc_len_max": int(lens.max())}
