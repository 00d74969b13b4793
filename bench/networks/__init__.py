"""Network builders, one module per configuration's ``builder``.

Each builds the configuration's network from the program's own actor
classes with the same topology as ``repro.apps.streams.NETWORKS[network]``,
fed by a source of the benchmark's own that replays given values.  In
``serve()`` the client is the source, so the source matters only to
``run()``.  ``build(config, values)`` returns ``(Network, {sink: list})``.
"""

from __future__ import annotations

from typing import Sequence


def replay_source(net, values: Sequence[float], name: str = "source"):
    """A host source that emits ``values`` in order, then stops."""
    vals = [float(v) for v in values]

    def gen(st):
        i = st.get("i", 0)
        return {**st, "i": i + 1}, vals[i]

    return net.source(name, gen, has_next=lambda st: st.get("i", 0) < len(vals))
