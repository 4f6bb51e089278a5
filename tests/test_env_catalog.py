"""Repo lint: every ``MXNET_*`` environment variable mentioned in
``mxnet_tpu/`` must resolve through the ``config.py`` catalog.

The catalog is what makes configuration discoverable
(``mx.config.list_env()``) and loudly validated; an env var read that
bypasses it is folklore with silent-failure semantics.  This test
names the offender and its location, so the new observability vars —
and every future one — can't sneak in unregistered."""

import os
import re

import mxnet_tpu.config as config

_PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mxnet_tpu")

_TOKEN = re.compile(r"MXNET_[A-Z0-9_]+")

# read sites: a token on one of these lines is an actual env READ and
# must be registered EXACTLY (doc prose gets prefix tolerance below)
_READ = re.compile(r"environ|get_env|getenv|_validated_env|"
                   r"_read_env|fleet_env|describe\(")


def _catalog():
    return {v.name for v in config.list_env()}


def test_every_env_read_resolves_through_the_catalog():
    registered = _catalog()
    offenders = []
    for root, _dirs, files in os.walk(_PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, os.path.dirname(_PKG))
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    for tok in _TOKEN.findall(line):
                        name = tok.rstrip("_")
                        if name in registered:
                            continue
                        if _READ.search(line):
                            # an actual read of an unregistered var
                            offenders.append(
                                f"{rel}:{lineno}: {tok} (read)")
                        elif not any(r.startswith(name + "_")
                                     for r in registered):
                            # prose may name a family ("MXNET_CHAOS_*")
                            # — anything else is an unregistered name
                            offenders.append(
                                f"{rel}:{lineno}: {tok} (mention)")
    assert not offenders, (
        "MXNET_* env vars bypassing the config.py catalog "
        "(register_env them):\n  " + "\n  ".join(offenders))


def test_catalog_has_no_dead_entries():
    """The inverse direction: every registered var is actually
    mentioned somewhere OUTSIDE config.py (a stale catalog entry
    documents configuration that nothing reads).  tests/ and tools/
    count — some vars are consumed by the harness."""
    repo = os.path.dirname(_PKG)
    mentioned = set()
    for sub in ("mxnet_tpu", "tests", "tools"):
        for root, _dirs, files in os.walk(os.path.join(repo, sub)):
            for fn in files:
                if fn.endswith(".py") and fn != "config.py":
                    with open(os.path.join(root, fn)) as f:
                        mentioned.update(_TOKEN.findall(f.read()))
    dead = sorted(_catalog() - mentioned)
    assert not dead, f"catalog entries never mentioned in code: {dead}"


def test_observability_vars_are_registered():
    """The PR-12 vars specifically (the satellite's motivating case)."""
    registered = _catalog()
    for name in ("MXNET_METRICS_PORT", "MXNET_FLIGHT_RECORDER",
                 "MXNET_FLIGHT_RECORDER_SIZE",
                 "MXNET_FLIGHT_RECORDER_DIR", "MXNET_TRACE_SAMPLE",
                 "MXNET_PEAK_TFLOPS"):
        assert name in registered, name
        assert config.describe(name).doc
