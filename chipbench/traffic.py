"""The one traffic generator: a mix file of parameters in, an arrival
schedule out.

A mix (``traffic/<name>.json``) describes sessions.  Each session brings
an optional shared document and one or more turns; every turn is one
request whose prompt is the document followed by the turn's own text.
Independent chat is a session with no document and one turn.

Sessions arrive as a Poisson process at the cell's rate, conditioned on
its count: ``rate * seconds`` sessions start in the window, each at a
uniform time drawn from the seed, so gaps are exponential and bursts come
as often as in any Poisson stream.  A lead-in of ``rate * lead_s``
sessions, drawn the same way over the ``lead_s`` seconds before the
window opens, brings the server to its steady load; those are served but
never measured.

The sizes of a run's work are fixed by the mix and the rate, not by the
seed: every length, budget and turn count is drawn as the evenly spaced
quantiles of its distribution, and the seed only orders them and draws
the token ids, so two seeds offer the same multiset of sizes.  The order
of sizes is stratified: the values are cut into ``BLOCK`` bands of
neighbouring quantiles, and every run of ``BLOCK`` consecutive sessions
takes one value from each band, in an order drawn from the seed, so a
seed cannot bunch the longest prompts or answers together.

Every prompt length lies on a fixed menu (see :func:`menu`), so the
programs a run needs are known before the window opens.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

_NORMAL = NormalDist()

#: sessions in a stratum: each run of this many takes one value from each
#: of this many quantile bands
BLOCK = 8


@dataclasses.dataclass
class Arrival:
    """One request of the schedule."""
    rid: int
    due_s: float            # offset from the start of the window
    prompt: List[int]
    budget: int             # output tokens; every request decodes all
    prefix_len: int         # tokens of the prompt shared with earlier
    #                         turns of its session (0: a first visit)
    session: int
    measured: bool = True   # a window session's; False in the lead-in


def menu(mix: Dict) -> List[int]:
    """Every prompt or suffix length the mix may produce, ascending."""
    m = mix["menu"]
    lens = set(range(m["step"], m["max"] + 1, m["step"]))
    lens.update(m.get("extra", ()))
    return sorted(lens)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _draw(spec: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the distribution ``spec`` names,
    clipped to its ``min``/``max``."""
    u = _quantiles(n)
    if "lognormal" in spec:
        p = spec["lognormal"]
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        v = p["median"] * np.exp(p["sigma"] * z)
    elif "exponential" in spec:
        v = -spec["exponential"]["mean"] * np.log1p(-u)
    elif "uniform_int" in spec:
        lo, hi = spec["uniform_int"]
        v = lo + np.floor(u * (hi - lo + 1))
    elif "choice" in spec:
        c = np.asarray(spec["choice"], np.float64)
        v = c[np.floor(u * len(c)).astype(int)]
    else:
        raise ValueError(f"unknown distribution in {spec}")
    return np.clip(v, spec.get("min", -np.inf), spec.get("max", np.inf))


def _snap_up(v: np.ndarray, lens: Sequence[int]) -> np.ndarray:
    arr = np.asarray(lens)
    return arr[np.minimum(np.searchsorted(arr, v), len(arr) - 1)]


def _doc_lengths(spec: Dict, n: int) -> np.ndarray:
    """Document lengths of the form ``align * k + offset``."""
    v = _draw(spec, n)
    a, off = spec["align"], spec["offset"]
    k = np.rint((v - off) / a)
    lo = math.ceil((spec["min"] - off) / a)
    hi = math.floor((spec["max"] - off) / a)
    return (np.clip(k, lo, hi) * a + off).astype(int)


def stratified(values: np.ndarray, rng) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``BLOCK`` consecutive entries holds one value from each of ``BLOCK``
    bands of neighbouring values."""
    if not len(values):
        return np.asarray(values)
    bands = [rng.permutation(b) for b in
             np.array_split(np.sort(values), min(BLOCK, len(values)))]
    out = []
    for r in range(max(len(b) for b in bands)):
        for i in rng.permutation(len(bands)):
            if r < len(bands[i]):
                out.append(bands[i][r])
    return np.asarray(out, dtype=np.asarray(values).dtype)


def _sessions(mix: Dict, n: int, rng) -> Dict[str, np.ndarray]:
    """The sizes of ``n`` sessions: quantiles in a stratified order."""
    doc_spec = mix.get("document")
    docs = (stratified(_doc_lengths(doc_spec, n), rng) if doc_spec
            else np.zeros(n, int))
    turns = stratified(_draw(mix.get("turns", {"uniform_int": [1, 1]}),
                             n).astype(int), rng)
    total = int(turns.sum())
    follow = stratified(_draw(mix.get("turn_gap_s",
                                      {"exponential": {"mean": 1.0}}),
                              total), rng)
    q_raw = _draw(mix["prompt"], total)
    if mix["prompt"].get("snap") == "menu_up":
        q_raw = _snap_up(q_raw, menu(mix))
    return {"docs": docs, "turns": turns, "follow": follow,
            "turn0": np.concatenate([[0], np.cumsum(turns)]).astype(int),
            "q_lens": stratified(q_raw.astype(int), rng),
            "outs": stratified(_draw(mix["output"], total).astype(int), rng)}


def schedule(mix: Dict, rate: float, seconds: float, seed: int,
             vocab: int, lead_s: float = 0.0) -> List[Arrival]:
    """The whole arrival schedule of one run, sorted by due time.

    ``rate`` is sessions per second.  Due times are offsets from the
    window's opening; the lead-in's are negative.  Arrivals stop at
    ``seconds``: a later turn of a session that would fall past it is not
    offered."""
    rng = np.random.default_rng(seed)
    n = max(int(round(rate * seconds)), 1)
    n_lead = int(round(rate * lead_s))
    starts = np.concatenate([np.sort(rng.uniform(-lead_s, 0.0, n_lead)),
                             np.sort(rng.uniform(0.0, seconds, n))])
    lead, win = _sessions(mix, n_lead, rng), _sessions(mix, n, rng)

    # token ids: the first token of every document, and of every turn
    # within a session, is distinct, so no prompt can match another's
    # first page by chance (a chance match would prefill an off-menu
    # suffix length)
    firsts = rng.permutation(np.arange(1, vocab))
    fi = 0
    out: List[Arrival] = []
    for s in range(n_lead + n):
        measured = s >= n_lead
        sz, i = (win, s - n_lead) if measured else (lead, s)
        k = int(sz["turn0"][i])
        doc: List[int] = []
        if sz["docs"][i]:
            doc = rng.integers(1, vocab, size=int(sz["docs"][i])).tolist()
            doc[0] = int(firsts[fi])
            fi += 1
        t = float(starts[s])
        for j in range(int(sz["turns"][i])):
            if j:
                t += float(sz["follow"][k])
            q = rng.integers(1, vocab, size=int(sz["q_lens"][k])).tolist()
            q[0] = int(firsts[fi])
            fi += 1
            if t < seconds:
                out.append(Arrival(rid=-1, due_s=t, prompt=doc + q,
                                   budget=int(sz["outs"][k]),
                                   prefix_len=len(doc) if j else 0,
                                   session=s, measured=measured))
            k += 1
    out.sort(key=lambda a: a.due_s)
    for i, a in enumerate(out):
        a.rid = i
    return out


def prefill_shapes(mix: Dict) -> Dict[str, List[int]]:
    """The prefill programs the mix can reach: ``plain`` prompt lengths
    (first visits, whole prompts) and ``suffix`` lengths prefilled after a
    prefix hit.  A hit on a document of ``align * k + offset`` tokens with
    ``-align < offset < 0`` ends inside a page, so the fork page is copied
    and exactly the turn's own tokens are prefilled."""
    lens = menu(mix)
    q = mix["prompt"]
    if "choice" in q:
        turn_lens = sorted({int(x) for x in q["choice"]})
    else:
        turn_lens = [x for x in lens
                     if x >= _snap_up(np.array([q.get("min", 0)]), lens)[0]
                     and x <= q.get("max", lens[-1])]
    doc = mix.get("document")
    if not doc:
        return {"plain": turn_lens, "suffix": []}
    a, off = doc["align"], doc["offset"]
    kmin = math.ceil((doc["min"] - off) / a)
    kmax = math.floor((doc["max"] - off) / a)
    first = sorted({k * a + off + t for k in range(kmin, kmax + 1)
                    for t in turn_lens})
    # a session whose document fell out of the cache (an idle re-entry
    # starts a fresh pool) sends its later turns whole: same lengths
    return {"plain": first, "suffix": turn_lens}


def max_context(mix: Dict) -> int:
    """Longest prompt plus output any request of the mix can reach."""
    shapes = prefill_shapes(mix)
    return int(max(shapes["plain"]) + mix["output"]["max"])

