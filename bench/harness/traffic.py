"""The one traffic generator: a traffic file's templates, drawn from a seed.

A traffic file is data (``bench/traffic/<name>.json``):

    loop       how requests arrive: the name of a driver of its own,
               ``bench/loops/<loop>.py`` (`harness.serve`), which reads
               whatever further keys it names (``rate`` for "open")
    clients    how many client streams (default 1)
    tenants    {"names": [...], "weights": [...]}, or null for one table
    templates  [{"name", "weight", "params", "query"}]: ``query`` is a
               `harness.queries` tree in which "{t}" inside a name stands
               for the tenant, and a string "$x" for the value of x in
               the drawn parameter set; ``params`` maps a parameter set's
               name to its list of choices, each a dict of values
    warm       {"max_group", "replay_ticks", "replay_passes"}: see
               `harness.serve.warm_up`

A request is drawn as: a template by weight, a tenant by weight, then one
choice from each of the template's parameter sets, uniformly. Client c of
a run with seed s draws from its own stream, seeded by (s, c), so the same
seed gives every client the same sequence of requests.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One drawn request: its description and what it is grouped by."""

    template: str
    tenant: Optional[str]
    params: Tuple[Tuple[str, int], ...]
    query: object                 # a `harness.queries` tree

    @property
    def shape(self) -> Tuple[str, Tuple[Tuple[str, int], ...]]:
        """Template and parameters, without the tenant: requests of one
        shape differ only in which tenant's vectors they name."""
        return self.template, self.params


def _fill(node, tenant: Optional[str], values: Dict[str, int]):
    if isinstance(node, str):
        if node.startswith("$"):
            return values[node[1:]]
        return node.replace("{t}", tenant) if tenant is not None else node
    if isinstance(node, list):
        return [_fill(a, tenant, values) for a in node]
    if isinstance(node, dict):
        return {k: _fill(v, tenant, values) for k, v in node.items()}
    return node


def instantiate(template: dict, tenant: Optional[str],
                choice: Dict[str, int]) -> Request:
    values = dict(choice)
    return Request(template["name"], tenant, tuple(sorted(values.items())),
                   _fill(template["query"], tenant, values))


class Traffic:
    """The drawing rules of one traffic file."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.loop: str = spec["loop"]
        self.clients = int(spec.get("clients", 1))
        self.templates: List[dict] = list(spec["templates"])
        w = np.asarray([t["weight"] for t in self.templates], float)
        self._template_p = w / w.sum()
        ten = spec.get("tenants")
        self.tenants: List[Optional[str]] = (list(ten["names"]) if ten
                                             else [None])
        tw = np.asarray(ten["weights"] if ten else [1.0], float)
        self._tenant_p = tw / tw.sum()
        self.warm = spec["warm"]

    def draw(self, rng: np.random.Generator) -> Request:
        t = self.templates[rng.choice(len(self.templates),
                                      p=self._template_p)]
        tenant = self.tenants[rng.choice(len(self.tenants),
                                         p=self._tenant_p)]
        choice: Dict[str, int] = {}
        for name in sorted(t["params"]):
            options = t["params"][name]
            choice.update(options[int(rng.integers(len(options)))])
        return instantiate(t, tenant, choice)

    def client_stream(self, seed: int, client: int) -> Iterator[Request]:
        rng = np.random.default_rng([seed, client])
        while True:
            yield self.draw(rng)

    def shapes(self) -> List[Request]:
        """One request of every shape the traffic can draw, for the first
        tenant: every template under every combination of choices."""
        out = []
        for t in self.templates:
            names = sorted(t["params"])
            for combo in itertools.product(*(t["params"][n] for n in names)):
                choice: Dict[str, int] = {}
                for c in combo:
                    choice.update(c)
                out.append(instantiate(t, self.tenants[0], choice))
        return out

    def with_tenant(self, req: Request, i: int) -> Request:
        """``req`` for the i-th tenant (cycling), same shape."""
        t = next(x for x in self.templates if x["name"] == req.template)
        return instantiate(t, self.tenants[i % len(self.tenants)],
                           dict(req.params))

