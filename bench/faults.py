"""Faults planted in the program where its answers are produced, for the
tests (``bench/tests/test_faults.py``) and for readings on the chip
(``readings.py --fault``).  ``correct`` has to come out false under each:

* ``unchanged``: the peel returns its state unchanged (no round runs);
* ``half_batch``: half of the batch left out: every other s-clique
  dropped from the incidence the build hands on, the peel run on the rest;
* ``core_altered``: one core number altered in the routed answer;
* ``label_altered``: one r-clique's nucleus altered in the forest's cut.

One chip has no exchange between chips, so that fault has no place here.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np


def _unchanged():
    import jax.numpy as jnp
    import repro.core.session as session

    def peel(problem, schedule, *, hierarchy=False, **_):
        n_r = problem.deg0.shape[0]
        out = (jnp.full((n_r,), -1, jnp.int32),
               jnp.full((n_r,), -1, jnp.int32), jnp.zeros((), jnp.int32))
        if hierarchy:
            out += (jnp.arange(n_r, dtype=jnp.int32),
                    jnp.full((n_r,), -1, jnp.int32))
        return out

    return mock.patch.object(session, "dense_coreness", peel)


def _half_batch():
    import jax.numpy as jnp
    import repro.core as core
    from repro.core.incidence import NucleusProblem
    real = core.build_problem

    def build(g, r, s, **kw):
        p = real(g, r, s, **kw)
        inc = np.asarray(p.inc_rid)[::2]
        deg0 = np.bincount(inc.reshape(-1), minlength=p.n_r)
        sids = np.argsort(inc.reshape(-1), kind="stable") // inc.shape[1]
        off = np.concatenate([[0], np.cumsum(deg0)])
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        return NucleusProblem(g=p.g, r=r, s=s, r_cliques=p.r_cliques,
                              inc_rid=i32(inc), mem_offsets=i32(off),
                              mem_sids=i32(sids), deg0=i32(deg0),
                              orientation=p.orientation,
                              build_stats=p.build_stats)

    return mock.patch.object(core, "build_problem", build)


def _core_altered():
    from repro.serve import Router
    real = Router.route

    def route(self, request):
        dec = real(self, request)
        dec._core = np.asarray(dec._core).copy()
        dec._core[0] += 1
        return dec

    return mock.patch.object(Router, "route", route)


def _label_altered():
    from repro.core.hierarchy import HierarchyTree
    real = HierarchyTree.ancestor_at_level

    def cut(self, c):
        labels = np.asarray(real(self, c)).copy()
        live = np.flatnonzero(labels >= 0)
        if live.size:
            labels[live[-1]] = labels.max() + 1
        return labels

    return mock.patch.object(HierarchyTree, "ancestor_at_level", cut)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "core_altered": _core_altered, "label_altered": _label_altered}


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted, inside the block."""
    with FAULTS[name]():
        yield
