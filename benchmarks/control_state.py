"""The control and the planted faults of a serving cell whose slots keep
more than a row of positions, beside ``control.py``. That file's faults
move a row's cache POSITION, and its control rounds the whole tree into a
float32 copy. ``nemotron3s-serve-chat`` has one attention layer in eleven
and no positional encoding, so it passes ``boundary`` and ``one_slot``
with its sound numbers (one position of a context moves its logits by
less than bfloat16 does), and 4.65B parameters in float32 are 18.6 GB, so
``reference_ladder`` ends ``RESOURCE_EXHAUSTED`` (my chip runs, PR 35).
These are the same three things for such a cell: a fault of the key/value
pool, a fault of one slot, the reference a precision below. The same run as
``run.py``'s with one thing wrong underneath; it has to end
``correct: false``, by the numbers named beside it (the readings: my chip
runs, PR 35, calls E and G, 8 s windows at the cell's size, three seeds a
plant):

    python3 benchmarks/control_state.py --plant <name> --workload <cell> --seed <n> --seconds <s> --trace 0

``reference_<scheme>``  the CONTROL of the contract, as ``control.py``'s:
    the plain reference in the program's place, every matrix of the stated
    weights rounded to ``scheme`` (``control.SCHEMES``; ``fp8_e4m3`` is
    ``control.CONTROL``) by ``control.rounded``, the forward in float32.
    Here a matrix is rounded where the reference WIDENS it (its ``f32``),
    one layer at a time and inside an expert layer one expert at a time,
    so no second copy of the weights is ever held (``e_median``,
    ``e_far_share``). On the chip: median of e 0.546, 0.553, 0.552 and
    every position far, where the program read 0.047 on the same samples.
``kv_heads_misordered``  the pack writes a prompt's keys and values into
    the pool with the order of the key/value heads reversed: with two
    heads of sixteen queries each, every query of a prompt's positions
    reads the other group's keys and values; what decode steps write is
    in order (``e_median``, ``e_far_share``). The fault a pool sized by
    KEY/VALUE heads invites; of the four tried at the cell's widths on
    the CPU (2 held experts a layer, one sequence of 320: crossed block
    tables e 0.18, the prompt's keys and values zero 0.34, no attention
    at all 0.42, this one 0.59) the one that moves the logits most,
    because each head's values have a mean of their own. On the chip:
    median of e 0.446, 0.519, 0.512, 0.955-0.996 of the positions far.
``lost_state``  at each request's FIRST decode step, where the state its
    prefill left is first read, the slot's recurrent rows are zeros: a
    prefill that handed over nothing (``e_far_share``, ``e_median``). On
    the chip, four seeds: 0.16-0.60 of the positions far, the median of e
    0.13-0.28.
``one_slot_state``  the same at EVERY step of one slot: the slot of the
    request that last arrived on an empty engine, so the replay, which
    starts on one, has it (its first request, the longest of the
    sample). One row of 128; the first round's fixed slot was one the
    replay's 32 requests need not use, and was caught on one seed of
    three (``e_far_share``, and ``replay_requests_that_differ`` where the
    window's tenant of that slot is in the sample). On the chip: 0.043,
    0.020, 0.024 of the positions far (109-235 of 4,700-5,500), one
    request that differs each time, the median of e as sound (0.047).

A cell whose model keeps no recurrent state has nothing for the last two
to lose, and a reference that widens nothing through an ``f32`` of its
own is ``control.py``'s to round.
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def first_decode_step(engine, active):
    return [s.slot for s in active if s.generated == 1]


def slot_of_who_came_alone(engine, active):
    if len(active) == 1 and active[0].generated == 1:
        engine.planted_slot = active[0].slot
    return [s.slot for s in active
            if s.slot == getattr(engine, "planted_slot", None)]


STATE_FAULTS = {"lost_state": first_decode_step,
                "one_slot_state": slot_of_who_came_alone}


def state_lost(slots_of):
    """A ``ServeEngine._decode`` before which the recurrent rows of the
    slots ``slots_of(engine, active)`` picks are zeros. One donated
    program whatever the slot, compiled in the warm-up."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.serving.engine import ServeEngine

    real = ServeEngine._decode
    cleared = jax.jit(lambda states, slot: jax.tree_util.tree_map(
        lambda a: a.at[slot].set(0), states), donate_argnums=0)

    def _decode(self, active):
        for slot in slots_of(self, active):
            states = iter(cleared(
                tuple(pool for kind, pool in zip(self._kinds, self._pools)
                      if kind == "recurrent"), jnp.int32(slot)))
            self._pools = tuple(
                next(states) if kind == "recurrent" else pool
                for kind, pool in zip(self._kinds, self._pools))
        return real(self, active)

    return _decode


def heads_misordered(pack):
    """``pack_prefill`` handed ``[kv layers, T, H, D]`` stacks whose heads
    are in reverse order; inside the engine's one pack program."""
    @functools.wraps(pack)
    def pack_prefill(pools, blocks, k_stack, v_stack, *more, **static):
        return pack(pools, blocks, k_stack[:, :, ::-1], v_stack[:, :, ::-1],
                    *more, **static)

    return pack_prefill


def reference_a_precision_below(driver, scheme):
    """Have ``driver.check_against_reference`` judge the reference with
    every matrix rounded to ``scheme`` where it would judge the replay's
    logits (``control.reference_in_the_programs_place``, without the
    float32 copy of the tree: the reference rounds what it widens)."""
    import jax
    from benchmarks import control
    from benchmarks.harness import say
    real = driver.check_against_reference

    def check(run, params, sample, served, held):
        reference = run.family.reference
        widen = reference.f32
        numbers = {}
        real(run, params, sample, served, numbers)
        say("the program as it is, before the control: " + ", ".join(
            f"{k} {v:.4f}" for k, (v, _) in numbers.items()))
        reference.f32 = lambda x: widen(control.rounded(x, scheme))
        try:        # traced here, with the rounding in it
            rows = control.reference_rows(
                run, jax.jit(run.family.reference_logits(run.config)),
                params, sample)
        finally:
            reference.f32 = widen
        numbers = {}
        real(run, params, sample, rows, numbers)
        say(f"CONTROL reference at {scheme}: " + ", ".join(
            f"{k} {v:.4f} (limit {limit:g})"
            for k, (v, limit) in numbers.items()))
        held.update(numbers)

    driver.check_against_reference = check


def plant(name):
    """Put ``name`` under the run; returns what undoes it."""
    from benchmarks import control
    from benchmarks import run as bench_run
    from deepspeed_tpu.serving import engine as serving

    if name in STATE_FAULTS:
        owner, attr = serving.ServeEngine, "_decode"
        value = state_lost(STATE_FAULTS[name])
    elif name == "kv_heads_misordered":
        owner, attr = serving, "pack_prefill"
        value = heads_misordered(serving.pack_prefill)
    elif name.startswith("reference_") and name[10:] in control.SCHEMES:
        owner, attr = bench_run, "load_module"

        def value(kind, module, real=bench_run.load_module):
            loaded = real(kind, module)
            if kind == "drivers":
                reference_a_precision_below(loaded, name[10:])
            return loaded
    else:
        raise SystemExit(
            f"no plant named {name!r}; there are {sorted(STATE_FAULTS)}, "
            f"kv_heads_misordered and reference_<scheme> for "
            f"{sorted(control.SCHEMES)}")
    real = getattr(owner, attr)
    setattr(owner, attr, value)
    return lambda: setattr(owner, attr, real)


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
