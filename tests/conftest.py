"""Test fixtures. NOTE: no XLA device-count flags here — smoke tests and
benches must see the real single CPU device; multi-device distribution tests
spawn subprocesses that set XLA_FLAGS themselves (see test_dist.py)."""

import numpy as np
import pytest

from repro.core import events as _events_mod
from repro.core import session as _session_mod


@pytest.fixture(autouse=True)
def pasta_root_session():
    """Open a fresh root Session per test (and reset the Event sequence
    counter), so outcomes never depend on collection order.  Tests get
    session-scoped isolation through the public session API instead of
    poking module globals; anything resolving the ambient PASTA pipeline
    (``pasta.region``, handler-less pools, the deprecation shims) lands in
    this per-test root session."""
    _events_mod.reset_seq()
    _session_mod.reset_state()
    yield _session_mod.root_session()
    _session_mod.reset_state()


@pytest.fixture()
def handler(pasta_root_session):
    """The per-test root session's handler (tools subscribe to it)."""
    return pasta_root_session.handler


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
