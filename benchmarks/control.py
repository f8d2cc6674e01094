"""The controls and the planted faults of the serving cells' reference
check: the same run as ``run.py``'s with one thing wrong underneath. Every
one of them has to end ``correct: false``, by the number named beside it.

    python3 benchmarks/control.py --plant <name> --workload <cell> --seed <n> --seconds <s> --trace 0

Same driver, same limits, same result line: a limit that one of these runs
passes is no limit. They are run by hand on the chip, at the cell's own
size, when a serving cell is admitted or a limit is set (PERF.md section 2
has the readings the limits stand between); the benchmark's own runs never
run them, and ``tests/yardstick/test_logits_check.py`` keeps each as a
test at a size a CPU holds.

Controls, a precision below the bfloat16 the configuration states:

``reference_<scheme>``  the CONTROL of the contract: the plain reference put
    in the program's place, every matrix of the stated weights rounded to
    ``scheme`` (``SCHEMES``) and the forward in float32; it does not
    decode: at each position of the window's own prompts and tokens its
    logits stand where the replay's would. Fails ``e_median``.
``reference_ladder``  every scheme in one process, each reading printed;
    ``correct`` is decided by ``CONTROL``'s.
``int8_path``  the program's own int8 path, ``init_inference(quantize=
    True)`` (every matrix int8 with a scale a column, dequantised inside
    the compiled programs; ``init_serving`` hands the keyword on), the
    reference still on the stated weights. The step a later PR would be
    tempted by, and the mildest of the ladder: it reads only 2.3 x what
    bfloat16 does, so it is a second witness and not the control.

Faults, planted in ``ServeEngine`` (requests still finish with the tokens
they asked for):

``altered_token``  every decode token + 1 where it is produced: the
    logits are sound, the token is not theirs (``gap_far_share``).
``prefill_token``  the same for every request's first token, the prefill
    program's (``first_gap_far_share``).
``replay_differs``  a decode whose tokens depend on how often it was
    called (``replay_requests_that_differ``).
``misplaced``  every decode step reads and writes one position back
    (``e_far_share``).
``boundary``  the same at each request's FIRST decode step only, where the
    prefill's cache is first read: one position a request.
``one_slot``  the same at every step of slot 0 only: one row of the batch.
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# scheme -> (largest value, explicit mantissa bits, least normal exponent);
# an int8 column or matrix is scaled so that its largest weight is 127, an
# 8-bit float matrix so that it is the format's largest finite value
SCHEMES = {"int8_column": (127.0, None, None),
           "int8_matrix": (127.0, None, None),
           "fp8_e5m2": (57344.0, 2, -14),
           "fp8_e4m3": (448.0, 3, -6)}
CONTROL = "fp8_e4m3"


def rounded(params, scheme):
    """The stated weights with every matrix (a leaf of two dimensions or
    more) rounded to ``scheme`` and back: symmetric, to nearest even, the
    scale a column of the matrix (``int8_column``, as the program's own
    path) or one a matrix. The 8-bit floats are rounded by arithmetic on
    the exponent, not by a cast: the TPU's compiler widens a float8 it has
    no unit for, and a cast there and back rounds nothing (my chip run,
    PR 34, call H: e read 0.0000)."""
    import jax
    import jax.numpy as jnp

    top, mantissa, least = SCHEMES[scheme]

    def leaf(w):
        if w.ndim < 2:
            return w
        w = w.astype(jnp.float32)
        axis = 0 if scheme == "int8_column" else None
        scale = jnp.maximum(jnp.abs(w).max(axis, keepdims=True), 1e-30) / top
        x = w / scale
        if mantissa is None:
            step = 1.0
        else:       # x = m * 2**e, 0.5 <= |m| < 1: a binade's step
            _, e = jnp.frexp(x)
            step = jnp.ldexp(jnp.float32(1.0),
                             jnp.maximum(e - 1, least) - mantissa)
        return jnp.round(x / step) * step * scale

    return jax.jit(lambda p: jax.tree_util.tree_map(leaf, p))(params)


def reference_rows(run, logits_fn, params, sample):
    """What ``replay_with_logits`` returns, from the reference: per
    request the float32 logits row behind every served token but its
    first, by one full forward over prompt + served tokens."""
    import numpy as np

    width = run.traffic["max_total_len"]
    block = 4
    rows = []
    for lo in range(0, len(sample), block):
        part = sample[lo:lo + block]
        ids = np.zeros((block, width), np.int32)
        for i, r in enumerate(part):
            ids[i, :len(r["tokens"])] = r["tokens"]
        logits = logits_fn(params, ids)
        for i, r in enumerate(part):
            rows.append(list(np.asarray(
                logits[i, len(r["prompt"]):len(r["tokens"]) - 1])))
    return rows


def reference_in_the_programs_place(driver, schemes):
    """Have ``driver.check_against_reference`` judge the reference at each
    of ``schemes`` where it would judge the replay's logits; the last
    scheme's numbers are the ones ``correct`` is decided by."""
    from benchmarks.harness import say
    real = driver.check_against_reference

    def check(run, params, sample, served, held):
        import jax
        logits_fn = jax.jit(run.family.reference_logits(run.config))
        numbers = {}
        real(run, params, sample, served, numbers)
        say("the program as it is, before the control: " + ", ".join(
            f"{k} {v:.4f}" for k, (v, _) in numbers.items()))
        for scheme in schemes:
            numbers = {}
            real(run, params, sample, reference_rows(
                run, logits_fn, rounded(params, scheme), sample), numbers)
            say(f"CONTROL reference at {scheme}: " + ", ".join(
                f"{k} {v:.4f} (limit {limit:g})"
                for k, (v, limit) in numbers.items()))
        held.update(numbers)

    driver.check_against_reference = check


def one_position_back(rows_of):
    """A ``ServeEngine._decode`` that reads and writes one position back
    in the rows ``rows_of(active)`` picks."""
    from deepspeed_tpu.serving.engine import ServeEngine
    real = ServeEngine._decode

    def _decode(self, active):
        back = rows_of(active)
        for seq in back:
            seq.pos -= 1
        try:
            return real(self, active)
        finally:
            for seq in back:
                seq.pos += 1

    return _decode


def plant(name):
    """Put ``name`` under the run; returns what undoes it."""
    import deepspeed_tpu
    from benchmarks import run as bench_run
    from deepspeed_tpu.serving.engine import ServeEngine

    undo = []

    def put(owner, attr, value):
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, value)

    def in_driver(patch):
        real = bench_run.load_module

        def load_module(kind, module):
            loaded = real(kind, module)
            if kind == "drivers":
                patch(loaded)
            return loaded

        put(bench_run, "load_module", load_module)

    real_decode = ServeEngine._decode
    real_first = ServeEngine._record_first_token
    calls = {"n": 0}

    def altered(self, active):
        toks, logits = real_decode(self, active)
        return [(t + 1) % self.model_cfg.vocab_size for t in toks], logits

    def unsteady(self, active):
        calls["n"] += 1
        toks, logits = real_decode(self, active)
        return [(t + calls["n"]) % self.model_cfg.vocab_size
                for t in toks], logits

    def first_altered(self, seq, first):
        return real_first(self, seq, (first + 1) % self.model_cfg.vocab_size)

    if name == "int8_path":
        put(deepspeed_tpu, "init_serving", functools.partial(
            deepspeed_tpu.init_serving, quantize=True))
    elif name == "reference_ladder":
        order = [s for s in SCHEMES if s != CONTROL] + [CONTROL]
        in_driver(lambda d: reference_in_the_programs_place(d, order))
    elif name.startswith("reference_") and name[10:] in SCHEMES:
        in_driver(lambda d: reference_in_the_programs_place(d, [name[10:]]))
    elif name == "altered_token":
        put(ServeEngine, "_decode", altered)
    elif name == "prefill_token":
        put(ServeEngine, "_record_first_token", first_altered)
    elif name == "replay_differs":
        put(ServeEngine, "_decode", unsteady)
    elif name == "misplaced":
        put(ServeEngine, "_decode", one_position_back(lambda active: active))
    elif name == "boundary":
        put(ServeEngine, "_decode", one_position_back(
            lambda active: [s for s in active if s.generated == 1]))
    elif name == "one_slot":
        put(ServeEngine, "_decode", one_position_back(
            lambda active: [s for s in active if s.slot == 0]))
    else:
        raise SystemExit(f"no plant named {name!r}")

    def undo_all():
        for owner, attr, value in reversed(undo):
            setattr(owner, attr, value)

    return undo_all


def main(argv=None) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import say

    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--plant")
    name = argv[at + 1]
    del argv[at:at + 2]
    undo = plant(name)
    say(f"CONTROL: {name} is planted under this run; it has to end "
        f"correct: false")
    try:
        return bench_run.main(argv)
    finally:
        undo()


if __name__ == "__main__":
    sys.exit(main())
