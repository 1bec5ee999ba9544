"""The program's own host spans in a traced window of the tiny closed-loop
cell: every span of the served tick is there, under plain names, nested as
the scheduler and the server open them, with each request's ``rid`` as a
span argument."""

from jax.profiler import ProfileData
import pytest

import tiny
import bench
import devtrace as tr

PREFIXES = ("bench.", "sched.", "server.")
PARENT = {
    "sched.step": "bench.tick",
    "sched.admit": "sched.step",
    "sched.chunk": "sched.step",
    "server.decode": "sched.step",
    "server.prepare": "server.decode",
    "server.migrate": "server.decode",
    "server.dispatch": "server.decode",
    "server.counts": "server.decode",
    "server.balance": "server.decode",
    "sched.sample": "sched.step",
    "sched.logits_to_host": "sched.sample",
}


@pytest.fixture(scope="module")
def spans():
    """[(name, start, end, thread line, stats)] of a traced 1 s window."""
    spec = tiny.spec()
    system = bench.System(spec, 2**33 + 5, use_kernels=False, log=lambda *a: None)
    run = bench.Run(system, 2**33 + 5, log=lambda *a: None)
    run.warm(True)
    with tr.capture(True) as h:
        run.window(1.0, True, traced=True)
    try:
        pd = ProfileData.from_file(h.path)
        out = [(ev.name, ev.start_ns, ev.end_ns, line.name, dict(ev.stats) if ev.stats else {})
               for plane in pd.planes if plane.name.startswith("/host")
               for line in plane.lines for ev in line.events
               if ev.name.startswith(PREFIXES)]
    finally:
        tr.discard(h)
        system.free()
    return out


@pytest.mark.parametrize("child", sorted(PARENT))
def test_each_span_nests_in_its_parent(spans, child):
    kids = [s for s in spans if s[0] == child]
    assert kids, f"no {child} span"
    parents = [s for s in spans if s[0] == PARENT[child]]
    for _, s, e, line, _ in kids:
        assert any(ps <= s and e <= pe and pl == line for _, ps, pe, pl, _ in parents), \
            (child, s, e)


def test_span_names_are_plain(spans):
    names = {s[0] for s in spans}
    assert names == set(PARENT) | {"bench.tick"}


@pytest.mark.parametrize("span", ["sched.admit", "sched.chunk"])
def test_requests_carry_their_rid(spans, span):
    tagged = [s for s in spans if s[0] == span and "rid" in s[4]]
    assert tagged, span
    assert all(int(s[4]["rid"]) >= 0 for s in tagged)
    if span == "sched.chunk":
        assert len(tagged) == sum(1 for s in spans if s[0] == span)
