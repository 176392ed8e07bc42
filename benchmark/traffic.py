"""The one traffic generator.  A traffic file gives

    {"loop": "closed", "clients": 1,
     "mix": [{"query": "q6", "weight": 1}]}

``loop`` is ``closed``: each client sends its next query when its last one
returned.  Every seed gets the same multiset of queries per cycle, in
another order: a cycle holds each query ``weight`` times (weights are whole
numbers), shuffled from the seed and the client's index.
"""
import json

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("loop") != "closed":
        raise ValueError(f"{path}: loop {spec.get('loop')!r} is not one "
                         "this generator knows (closed)")
    if int(spec["clients"]) < 1 or not spec["mix"]:
        raise ValueError(f"{path}: needs clients >= 1 and a mix")
    for m in spec["mix"]:
        if int(m["weight"]) != m["weight"] or m["weight"] < 1:
            raise ValueError(f"{path}: weights are whole numbers >= 1")
    return spec


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
