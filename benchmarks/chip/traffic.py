"""The one generator of serving traffic. A mix is a data file under
``traffic/`` (see ``bench.traffic_file``); this reads its parameters:

  rate_per_s        open-loop arrival rate
  prompt_lengths    prompt length buckets (tokens), and
  prompt_weights    the share of requests in each
  gen_min, gen_max  output tokens, uniform between the two (inclusive)
  max_len           cache slots per request (longest prompt + longest output)
  repeat_share      share of requests that repeat a popular (prompt, gen) pair
  popular, zipf_s   size of the popular set and the Zipf exponent over it
  schedule_seed     fixes which request gets which lengths and arrival gap

Every seed gets the same work: the number of requests, the multiset of
lengths and of arrival gaps, and their order follow from the mix alone (its
``schedule_seed``), so runs on different seeds queue alike. The run's seed
draws the token ids and so the prompts; the same seed gives the same prompts.
Arrival gaps are the quantiles of an exponential at the mix's rate (a Poisson
process with its sampling noise taken out), scaled to span the window.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    due: float  # seconds after the window opens
    prompt: np.ndarray  # (prompt_len,) int32
    gen: int
    popular: int = -1  # rank in the popular set, -1 for a unique request

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def _largest_remainder(total: int, weights) -> list:
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: total - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def shape(mix: dict, seconds: float) -> dict:
    """The seed-independent part: per request its due time, prompt length,
    output length and popular rank."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / mix["rate_per_s"]
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lengths = np.repeat(
        mix["prompt_lengths"], _largest_remainder(n, mix["prompt_weights"])
    )
    lengths = rng.permutation(lengths)
    lo, hi = int(mix["gen_min"]), int(mix["gen_max"])
    gens = rng.permutation(
        np.floor(lo + (hi - lo + 1) * (np.arange(n) + 0.5) / n).astype(int)
    )
    popular = np.full(n, -1)
    n_rep = int(round(n * float(mix.get("repeat_share", 0.0))))
    if n_rep:
        ranks = np.arange(1, int(mix["popular"]) + 1)
        counts = _largest_remainder(n_rep, ranks ** -float(mix["zipf_s"]))
        slots = np.sort(rng.choice(n, size=n_rep, replace=False))
        popular[slots] = rng.permutation(np.repeat(np.arange(len(ranks)), counts))
        # every occurrence of a popular pair has the lengths of its first
        for k in np.unique(popular[slots]):
            at = np.flatnonzero(popular == k)
            lengths[at], gens[at] = lengths[at[0]], gens[at[0]]
    return {"due": due, "prompt_len": lengths, "gen": gens, "popular": popular}


def schedule(mix: dict, seconds: float, seed_words: list, vocab: int) -> list:
    """The requests due in a window of ``seconds``, in due order."""
    s = shape(mix, seconds)
    rng = np.random.default_rng(seed_words)
    pop_prompts: dict = {}
    out = []
    for i in range(len(s["due"])):
        k, length = int(s["popular"][i]), int(s["prompt_len"][i])
        if k >= 0:
            if k not in pop_prompts:
                pop_prompts[k] = rng.integers(0, vocab, length, dtype=np.int32)
            prompt = pop_prompts[k]
        else:
            prompt = rng.integers(0, vocab, length, dtype=np.int32)
        out.append(Request(float(s["due"][i]), prompt, int(s["gen"][i]), k))
    return out


def warmup_prompts(mix: dict, seconds: float, seed_words: list, vocab: int) -> list:
    """One prompt per prompt length the window uses, to compile each shape,
    drawn from a stream of their own so that none is a window's prompt."""
    rng = np.random.default_rng([*seed_words, 1])
    used = sorted(set(int(x) for x in shape(mix, seconds)["prompt_len"]))
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in used]
