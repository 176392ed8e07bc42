"""The one traffic generator.  A traffic file gives a closed or an open loop.

Closed::

    {"loop": "closed", "clients": 1,
     "mix": [{"query": "q6", "weight": 1}]}

Each client sends its next query when its last one returned.  Every seed
gets the same multiset of queries per cycle, in another order: a cycle holds
each query ``weight`` times (weights are whole numbers), shuffled from the
seed and the client's index.  A closed loop runs each query's validation
substitutions.

Open::

    {"loop": "open", "rate_qps": 2.0, "burst": 1,
     "tenants": [{"name": "dash", "weight": 3, "priority": 0}, ...],
     "mix": [{"query": "q6", "weight": 4, "distinct": 64, "zipf": 1.1}, ...]}

Arrivals come on a schedule, whatever is still in flight.  They come in
groups of ``burst`` (default 1), all of a group due at one instant; the
groups are a Poisson process at ``rate_qps / burst``, so the mean rate is
``rate_qps``.  The schedule is drawn whole, up front, from the seed: each
arrival's due time, query, substitution set, tenant and priority.  Each
query draws ``distinct`` substitution sets (default: one, its validation
values) from the ranges its file declares (``SUBSTITUTIONS``); an arrival
picks among them by rank with probability proportional to
``rank ** -zipf`` (default 0: uniformly).

So that every seed gets the same work in another order, the counts are fixed
and only the order is drawn: a window of ``seconds`` holds
``round(rate_qps * seconds / burst)`` groups (at least one), placed as a
Poisson process conditioned on that count (uniform times, sorted); the
arrivals of each query, of each tenant and of each rank within a query are
their weights' shares of the whole, by largest remainder, shuffled.
"""
import collections
import itertools
import json

import numpy as np

OPEN_KEYS = {"loop", "rate_qps", "burst", "tenants", "mix"}
OPEN_MIX_KEYS = {"query", "weight", "distinct", "zipf"}
TENANT_KEYS = {"name", "weight", "priority"}

Arrival = collections.namedtuple(
    "Arrival", "due query sub tenant priority")
Arrival.__doc__ = """One arrival of an open loop: ``due`` seconds after the
window's start, ``query`` with the substitution set ``sub`` (a dict, empty
for the validation values), for ``tenant`` at ``priority``."""


def _whole(x, least: int) -> bool:
    return isinstance(x, (int, float)) and int(x) == x and x >= least


def load(path: str, set_counts=None) -> dict:
    """The traffic file, checked.  ``set_counts(query)`` is how many
    substitution sets a query's file declares (1 where it declares none);
    an open file that asks for more ``distinct`` sets is refused."""
    with open(path) as f:
        spec = json.load(f)
    loop = spec.get("loop")
    if loop == "closed":
        if int(spec["clients"]) < 1 or not spec["mix"]:
            raise ValueError(f"{path}: needs clients >= 1 and a mix")
    elif loop == "open":
        _check_open(path, spec, set_counts)
    else:
        raise ValueError(f"{path}: loop {loop!r} is not one this generator "
                         "knows (closed, open)")
    for m in spec["mix"]:
        if not _whole(m["weight"], 1):
            raise ValueError(f"{path}: weights are whole numbers >= 1")
    return spec


def _check_open(path: str, spec: dict, set_counts) -> None:
    def refuse(why):
        raise ValueError(f"{path}: {why}")

    if set(spec) - OPEN_KEYS:
        refuse(f"unknown keys {sorted(set(spec) - OPEN_KEYS)}")
    rate = spec.get("rate_qps")
    if not isinstance(rate, (int, float)) or not rate > 0:
        refuse("rate_qps is a number > 0")
    if not _whole(spec.get("burst", 1), 1):
        refuse("burst is a whole number >= 1")
    tenants = spec.get("tenants")
    if not tenants:
        refuse("an open loop needs tenants")
    for t in tenants:
        if set(t) != TENANT_KEYS or not _whole(t["weight"], 1) \
                or not _whole(t["priority"], -2**31):
            refuse("a tenant is {name, weight >= 1, priority}, whole numbers")
    if len({t["name"] for t in tenants}) != len(tenants):
        refuse("tenant names repeat")
    if not spec.get("mix"):
        refuse("an open loop needs a mix")
    for m in spec["mix"]:
        if set(m) - OPEN_MIX_KEYS:
            refuse(f"unknown mix keys {sorted(set(m) - OPEN_MIX_KEYS)}")
        if not _whole(m.get("distinct", 1), 1):
            refuse("distinct is a whole number >= 1")
        zipf = m.get("zipf", 0)
        if not isinstance(zipf, (int, float)) or zipf < 0:
            refuse("zipf is a number >= 0")
        have = set_counts(m["query"]) if set_counts else None
        if have is not None and m.get("distinct", 1) > have:
            refuse(f"{m['query']} asks for {m['distinct']} distinct "
                   f"substitution sets; its ranges allow {have}")


def query_names(spec: dict) -> list:
    """Distinct queries of the mix, in file order."""
    return list(dict.fromkeys(m["query"] for m in spec["mix"]))


def client_stream(spec: dict, seed: int, client: int):
    """Endless sequence of query names for one client."""
    cycle = [m["query"] for m in spec["mix"] for _ in range(int(m["weight"]))]
    rng = np.random.default_rng([int(seed), 7, int(client)])
    while True:
        for i in rng.permutation(len(cycle)):
            yield cycle[i]


# -- substitution sets ------------------------------------------------------

def substitution_space(declared) -> list:
    """Every substitution set of a query file's ``SUBSTITUTIONS`` (name ->
    the values the spec allows), in a fixed order; ``[{}]`` where the file
    declares none."""
    if not declared:
        return [{}]
    names = list(declared)
    return [dict(zip(names, values))
            for values in itertools.product(*(declared[n] for n in names))]


def sub_key(sub: dict) -> tuple:
    """A substitution set as a key: its (name, value) pairs, sorted."""
    return tuple(sorted(sub.items()))


def draw_sets(spec: dict, seed: int, spaces: dict) -> dict:
    """query -> the run's substitution sets, in rank order: ``distinct`` of
    ``spaces[query]`` drawn from the seed without replacement, or the
    validation values alone where the mix gives no ``distinct``."""
    sets = {}
    for i, m in enumerate(spec["mix"]):
        if "distinct" not in m:
            sets[m["query"]] = [{}]
            continue
        space = spaces[m["query"]]
        rng = np.random.default_rng([int(seed), 13, i])
        sets[m["query"]] = [space[j] for j in
                            rng.choice(len(space), int(m["distinct"]),
                                       replace=False)]
    return sets


# -- the open schedule ------------------------------------------------------

def apportion(n: int, weights) -> list:
    """``n`` split by ``weights`` into whole counts: each its floor of the
    exact share, then one more to the largest remainders (ties: first)."""
    w = np.asarray(weights, dtype=float)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    left = n - int(counts.sum())
    for i in sorted(range(len(w)), key=lambda i: -(exact[i] - counts[i]))[:left]:
        counts[i] += 1
    return counts.tolist()


def _shuffled(rng, labels, weights, n) -> list:
    out = [x for x, c in zip(labels, apportion(n, weights)) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def open_schedule(spec: dict, seed: int, seconds: float, sets: dict) -> list:
    """The arrivals due in ``[0, seconds)``, sorted by due time (see the
    module's doc).  ``sets`` is ``draw_sets``'s."""
    rng = np.random.default_rng([int(seed), 17])
    burst = int(spec.get("burst", 1))
    groups = max(1, round(spec["rate_qps"] * seconds / burst))
    dues = np.repeat(np.sort(rng.uniform(0.0, seconds, groups)), burst)
    n = len(dues)
    queries = _shuffled(rng, [m["query"] for m in spec["mix"]],
                        [m["weight"] for m in spec["mix"]], n)
    tenants = _shuffled(rng, spec["tenants"],
                        [t["weight"] for t in spec["tenants"]], n)
    picks = {}
    for m in spec["mix"]:
        q = m["query"]
        ranks = np.arange(1, len(sets[q]) + 1, dtype=float)
        picks[q] = iter(_shuffled(rng, sets[q],
                                  ranks ** -float(m.get("zipf", 0)),
                                  queries.count(q)))
    return [Arrival(float(due), q, next(picks[q]), t["name"],
                    int(t["priority"]))
            for due, q, t in zip(dues, queries, tenants)]
