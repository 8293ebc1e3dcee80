"""The serve loop against a stand-in engine on a stand-in clock: how much
work a window holds may not depend on the seed. The engine costs what cell
3's does on the chip (PERF.md section 6: 1.2243 s a decode round whatever
the batch holds, 62 ms a prefill), so the window's arithmetic is the
cell's; the numbers are the stand-in's, not a device's."""

import types

from benchmarks.harness import manifest, serve, stats
from benchmarks.harness.tracing import Tracer

ROUND_S, PREFILL_S = 1.2243, 0.062


class Clock:
    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        self.t += 1e-4                 # a read costs something
        return self.t

    def sleep(self, s):
        self.t += s


class Seq:
    def __init__(self, prompt, n_out):
        self.prompt, self.max_new_tokens = prompt, n_out
        self.num_generated = 0
        self.n_preemptions = 0

    @property
    def done(self):
        return self.num_generated >= self.max_new_tokens

    @property
    def num_tokens(self):
        return len(self.prompt) + self.num_generated


class Engine:
    """``ServeEngine``'s surface as the loop uses it: a round prefills
    every waiting request (one token each), then decodes those that were
    running before (one token each)."""

    def __init__(self, clock):
        self.clock = clock
        self.seqs, self.decode_step_times = {}, []
        self.tokens_generated = self.prefills = 0
        self.sched = types.SimpleNamespace(waiting=[], running=[],
                                           has_work=False)

    def add_request(self, prompt, n_out):
        sid = len(self.seqs)
        self.seqs[sid] = Seq(prompt, n_out)
        self.sched.waiting.append(self.seqs[sid])
        self.sched.has_work = True
        return sid

    def step(self):
        sch = self.sched
        decode = list(sch.running)
        for s in sch.waiting:
            self.clock.t += PREFILL_S
            self.prefills += 1
            s.num_generated += 1
            self.tokens_generated += 1
        sch.running += sch.waiting
        sch.waiting = []
        if decode:
            self.clock.t += ROUND_S
            self.decode_step_times.append(ROUND_S)
            for s in decode:
                if not s.done:
                    s.num_generated += 1
                    self.tokens_generated += 1
        sch.running = [s for s in sch.running if not s.done]
        sch.has_work = bool(sch.running or sch.waiting)


def _run(traffic, seed, seconds, monkeypatch):
    clock = Clock()
    monkeypatch.setattr(serve, "time", clock)
    eng = Engine(clock)
    prog = serve.ServeProgram(engine=eng, vocab=50257, check=None,
                              attention={}, programs={}, info={})
    lines = {}
    res = serve.run_serve(prog, traffic, seed, seconds, Tracer(False, ""),
                          lambda phase, **kw: lines.__setitem__(phase, kw))
    return res, lines, eng


def test_cell_3_holds_the_same_work_whatever_the_seed(monkeypatch):
    traffic = manifest.load_traffic("serve-closed-c64", manifest.BENCH_DIR)
    rates, work = [], set()
    for seed in range(12):
        res, lines, eng = _run(traffic, seed, 20.0, monkeypatch)
        wave = lines["first-wave"]
        assert wave["running"] == 64 and wave["ended_in_setup"] == 0
        assert res["correct"] and lines["serve-window"]["in_flight_at_end"] \
            == 64
        rates.append(res["end_to_end"]["serve_tokens_per_s"])
        work.add((res["tokens"], eng.prefills - 64, res["attempted"],
                  lines["serve-window"]["completed"]))
    assert len(work) == 1, work             # tokens, prefills, requests
    assert stats.spread(rates) < 1e-4
