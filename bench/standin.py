"""The reference put in the program's place, for the controls.

A step kind's ``Control`` (``bench/steps/<kind>.py``) subclasses
:class:`ReferenceStandIn` with a reference that breaks one guarantee of the
configuration; ``bench/control.py`` runs the whole benchmark with it where
the store would be.  The graphs here are host Python.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.reference import ReferenceGraph


class ReferenceStandIn:
    """What the harness asks of a graph, answered by a reference graph."""

    ref_class = ReferenceGraph

    def __init__(self, v_capacity: int, e_capacity: int):
        self.ref = self.ref_class()
        self.state = SimpleNamespace(v_capacity=v_capacity, e_capacity=e_capacity)

    def apply(self, ops, us, vs):
        return np.asarray(self.ref.apply_all(np.asarray(ops), np.asarray(us), np.asarray(vs)), bool)

    def snapshot(self):
        return self.ref.vertices(), self.ref.edges()
