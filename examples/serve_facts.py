"""Concurrent fact serving in ~60 lines: a writer thread streams edges
into a transitive-closure store while reader threads serve snapshot-
isolated point queries — every result pinned to one MVCC token, repeat
queries folded from signed delta windows, point probes coalesced into
batched device calls.

    PYTHONPATH=src python examples/serve_facts.py --backend numpy
"""

from __future__ import annotations

import argparse
import dataclasses
import threading


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="numpy")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--chains", type=int, default=4)
    ap.add_argument("--hops", type=int, default=6)
    ap.add_argument("--appends", type=int, default=6)
    ap.add_argument("--reads", type=int, default=12)
    args = ap.parse_args()
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
    from repro.core.conditions import AddAction, cond, term
    from repro.serve import FactServer

    cfg = dataclasses.replace(EngineConfig.infer1(args.backend),
                              eval_mode="delta", shards=args.shards)
    e = HiperfactEngine(cfg)
    e.add_rules([
        Rule("base", (cond("edge", "?x", "to", "?y"),),
             (AddAction("path", term("?x"), "to", term("?y")),)),
        Rule("rec", (cond("edge", "?x", "to", "?y"),
                     cond("path", "?y", "to", "?z")),
             (AddAction("path", term("?x"), "to", term("?z")),)),
    ])
    e.insert_facts([Fact("edge", f"c{j}_n{i}", "to", f"c{j}_n{i + 1}")
                    for j in range(args.chains) for i in range(args.hops)])
    e.infer()

    with FactServer(e) as srv:
        q = [cond("path", "c0_n0", "to", "?z")]

        def writer() -> None:
            for i in range(args.appends):
                srv.append([Fact("edge", f"c0_n{args.hops + i}", "to",
                                 f"c0_n{args.hops + i + 1}")])

        def reader(r: int) -> None:
            for i in range(args.reads):
                res = srv.serve(q, tenant=f"tenant{r}")
                print(f"  tenant{r} read {i}: {len(res.rows)} rows "
                      f"via {res.mode}")

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = srv.stats()
        print(f"served modes: {st['served']}")
        print(f"requery: {st['requery']}")
        final = srv.serve(q)
        print(f"final frontier: {len(final.rows)} hops reachable "
              f"from c0_n0 at token {final.token[:1]}...")


if __name__ == "__main__":
    main()
