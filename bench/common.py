"""What the harness and every traffic driver share: finding a piece of
the benchmark by name, the run seed's random streams, a seeded sample,
and the base of a driver.

A traffic file (`traffic/<mix>.json`) names its `driver`, the depth `k`
and its parameters; a configuration file (`configs/<config>.json`) names
its graph `generator` with the generator's parameters and states the
guarantees.  The driver module `drivers/<driver>.py` defines `Driver`
(built from both and the run's seed) and `control`; the harness calls,
in order:

  setup()          make the graph from the seed, build the program's
                   state, warm every shape the window will use
  step()           one unit of window work (a build, a cycle, a call)
  end_to_end(s)    the end-to-end metrics over the window's `s` seconds
  work()           counts the per-layer readers need
  attempted()      units of work the window attempted
  release()        keep the window's results, drop the program's state
  check()          compare the window's results with the reference

`control(driver, steps, **options)` puts the plain reference, with one
stated guarantee broken, in the place of the program's results of a
window of `steps` steps; `check()` then has to come out as not correct.
A new kind of work is a new driver file, a new graph family a new
generator file: neither edits a file that is there.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

from bench import graphs

BENCH = os.path.dirname(os.path.abspath(__file__))

# independent random streams drawn from one run seed
GRAPH, TRAFFIC, WARMUP, SAMPLE = 0, 1, 2, 3

_MODULES: dict = {}


def load_module(kind: str, name: str):
    """The module `<kind>/<name>.py` under the benchmark's directory."""
    path = os.path.join(BENCH, kind, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise KeyError(f"no {kind} named {name!r} ({path})")
        mod_name = f"bench.{kind}._" + "".join(
            c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream; stream GRAPH gives the same graph
    as the program's generator at this seed."""
    seed = int(seed) & (2 ** 64 - 1)
    if stream == GRAPH:
        return np.random.default_rng(seed)
    return np.random.default_rng([seed, stream])


def to_program_graph(g: graphs.EdgeSet):
    from repro.graph.storage import Graph
    return Graph(g.node_labels, g.src, g.dst, g.elabel)


class Reservoir:
    """A uniform sample of at most `size` items of a stream, drawn from
    the seed (Algorithm R)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen, self.items = size, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.items[j] = item


class Driver:
    """The base of every driver: the edge law, depth, semantics and
    limits of one cell, and the seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.law = graphs.EdgeLaw(
            load_module("generators", config["generator"]), config["params"])
        self.k = int(traffic["k"])
        self.mode = config["mode"]
        self.limits = dict(traffic["limits"])

    def make_graph(self) -> graphs.EdgeSet:
        return self.law.graph(rng_for(self.seed, GRAPH))

    def checks(self, values: dict) -> dict:
        return {name: {"value": values[name], "limit": self.limits[name]}
                for name in self.limits}


def make_driver(config: dict, traffic: dict, seed: int) -> Driver:
    return load_module("drivers", traffic["driver"]).Driver(
        config, traffic, seed)
