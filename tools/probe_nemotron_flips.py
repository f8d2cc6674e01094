"""What a flipped expert choice costs, on the chip at the cell's own size:
the served model's bfloat16 forward against the benchmark's float32
reference on the SAME seeded bfloat16 weights, once with the model's own
routing and once with the reference's choices forced on it (ISSUE 35, part
4; ``tests/test_nemotron_h.py`` reads the same at the rehearsal's size).

    python3 tools/probe_nemotron_flips.py [--workload nemotron3s-serve-chat] [--seeds 1,2,3] [--rows 4] [--seq 512] [--one-pass]

Per seed one line: the median, 95th percentile and maximum of e (a
position's max over the vocabulary of |model - reference| in units of the
standard deviation of that position's reference logits, what
``drivers/serve_open_loop.py`` holds) and the shares of positions over
0.15, 0.2 and 0.25 (the driver's ``E_FAR``; the nearer two have the counts
to compare two programs by), both ways, and per expert layer the share of
tokens whose chosen set differs from the reference's. ``--one-pass`` reads
the same with the shared expert's inputs rounded once (``moe/dropless.py:
_in_two_halves`` replaced by one pass) beside the model as served. Nothing is forced
anywhere else: this is a probe, not a path of the program or of the
benchmark.
"""

import argparse
import os
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

E_FAR = 0.25


def main(argv=None) -> int:
    from benchmarks.harness import load_module, open_cell
    from benchmarks.reference import nemotron_h as reference
    from deepspeed_tpu.moe import dropless

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="nemotron3s-serve-chat")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--one-pass", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    _, _, config, _ = open_cell(args.workload, args.rehearsal)
    family = load_module("families", config["family"])
    model, _ = family.build_model(config)
    passes = {"as served": dropless._in_two_halves}
    if args.one_pass:
        passes["one pass"] = lambda dense, x, dtype: dense(x.astype(dtype))
    ref_config = family.reference_config(config)
    real_route = dropless.route

    def seeded(key):
        params = model.init({"params": key}, family.example_batch())["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                      params)

    def by_reference(params, ids):
        record = []
        return reference.logits(params, ids, ref_config, record), record

    def by_model(halves, params, ids, forced=None):
        noted = []

        def route(*a, **kw):
            noted.append(real_route(*a, **kw) if forced is None
                         else forced[len(noted)])
            chosen, weights = noted[-1]
            return chosen.astype(jnp.int32), weights

        dropless.route, dropless._in_two_halves = route, halves
        try:
            logits = model.apply({"params": params},
                                 {"input_ids": ids})["logits"]
        finally:
            dropless.route = real_route
            dropless._in_two_halves = passes["as served"]
        return logits, noted

    seeded, by_reference = jax.jit(seeded), jax.jit(by_reference)
    models = {label: jax.jit(partial(by_model, halves))
              for label, halves in passes.items()}
    print(f"{args.workload} on {jax.devices()[0].device_kind!r}: "
          f"[{args.rows}, {args.seq}] ids a seed, bfloat16 model v float32 "
          f"reference on the same weights", flush=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        params = seeded(jax.random.PRNGKey(seed))
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            0, config["vocab_size"], (args.rows, args.seq)), jnp.int32)
        want, theirs = by_reference(params, ids)
        spread = want.std(-1)
        order = lambda routing: np.sort(np.asarray(routing[0]), -1)
        for label, run in models.items():
            own, mine = run(params, ids)
            forced, _ = run(params, ids, theirs)
            said = []
            for name, got in (("own routing", own), ("forced", forced)):
                e = np.asarray(jnp.abs(got - want).max(-1) / spread).ravel()
                said.append(
                    f"{name}: e median {np.median(e):.4f} p95 "
                    f"{np.percentile(e, 95):.4f} max {e.max():.4f} share over"
                    + ",".join(f" {far:g} {np.mean(e > far):.5f}"
                               for far in (0.15, 0.2, E_FAR)))
            flipped = [float(np.mean((order(a) != order(b)).any(-1)))
                       for a, b in zip(mine, theirs)]
            print(f"seed {seed}, {label}: " + "; ".join(said) + "; share of "
                  f"tokens whose chosen experts differ, by expert layer: "
                  + " ".join(f"{f:.3f}" for f in flipped)
                  + f"; spread of the logits {float(jnp.median(spread)):.4f}",
                  flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
