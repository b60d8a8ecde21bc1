"""The program's own profiler spans (``repro:<layer>/<phase>``) and JAX's
compile events in a trace leave the benchmark's reduction as it was: the
loader keeps the ``bench:`` spans alone, so every reading of the trace,
and with it each per-layer metric, is what it would be without them."""
from __future__ import annotations

import small_cells  # noqa: F401  (puts the benchmark on the path)
import trace_reduce as tr


def test_loader_drops_the_program_spans(tmp_path):
    import numpy as np
    from common import Spans, TraceWindow
    from repro.core import decisions as dec
    from repro.core.offload import LayerCost
    from repro.hw import get_device
    from repro.obs.analyze import idle
    layers = [LayerCost(f"l{i}", flops=1e9 * (i + 1), act_bytes=1e4)
              for i in range(6)]
    spans = Spans()
    tw = TraceWindow(True, str(tmp_path), 0.0, 10.0, spans)
    tw.poll(0.0)
    for n in (40, 48):
        with spans("make_envs"):
            envs = dec.make_envs(get_device("pi5-arm"),
                                 get_device("edge-server-a100"),
                                 link_bw=np.geomspace(1e5, 1e9, n))
        with spans("decide_all"):
            dec.decide_all(layers, envs, backend="pallas")
    tw.stop()
    ev = tr.load(str(tmp_path))
    assert sorted({n for n, _, _ in ev.host}) == ["decide_all", "make_envs",
                                                  "window"]
    program = idle.load(str(tmp_path))
    assert {n for n, *_ in program.spans} >= {"decide/envs", "decide/call",
                                              "decide/kernel"}
    assert program.compiles
