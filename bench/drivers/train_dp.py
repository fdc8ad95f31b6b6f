"""Data-parallel training on the cell's chips: the program's
``DataParallelTrainer`` (``repro.distributed.trainer``) with the traffic's
gradient exchange (``sync``), through its ``step_fn()``, in the ``train``
driver's set-up, window and profile.  ``batch`` counts the rows of all the
chips together; the loader's rows are split over them, and the weights and
optimizer state are replicated on them.

The comparison is ``train``'s: the plain reference runs the same rows one
by one on one chip.  Beside ``train``'s planted faults, ``no_sync`` leaves
the exchange out: each chip updates from the gradient of its own rows.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

import harness

train = harness.module("drivers", "train")
FAULTS = train.FAULTS + ("no_sync",)
reference_readings = train.reference_readings
check = train.check


def replicated(tree, sharding, delete: bool):
    """``tree`` on ``sharding``, moved leaf by leaf (a leaf already there is
    kept), deleting each source leaf after its move when ``delete`` and the
    move copied it: the first step's weights and state then need no second
    whole copy on the chip that made them."""
    def move(x):
        if x.sharding == sharding:
            return x
        y = jax.device_put(x, sharding).block_until_ready()
        src = x.unsafe_buffer_pointer()
        if delete and all(s.data.unsafe_buffer_pointer() != src
                          for s in y.addressable_shards):
            x.delete()
        return y
    return jax.tree_util.tree_map(move, tree)


@contextlib.contextmanager
def data_parallel_steps(cell):
    """``train.make_step`` replaced by the trainer's step on the cell's
    chips for the duration of the block, and ``train.faulty_step``'s
    ``half_batch`` by the step fed the first half of the batch twice (the
    same gradient as the half alone, in a batch the chips still divide).
    ``no_sync`` is the trainer with an exchange that returns each chip's
    own gradient: the weights, still marked replicated, then differ from
    chip to chip, and a read-back sees one chip's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.collectives import SyncStrategy
    from repro.distributed.trainer import DataParallelTrainer

    NO_SYNC = SyncStrategy("no_sync", lambda grads, axis, dp: grads)

    def make_step(pcfg, run, opt, donate=True, strategy=None):
        trainer = DataParallelTrainer(
            pcfg, run, opt, strategy=strategy or cell.traffic["sync"],
            devices=jax.devices()[:cell.chips])
        inner = trainer.step_fn()
        rep, rows = NamedSharding(trainer.mesh, P()), trainer.batch_sharding()

        def step(p, s, b):
            p, s = replicated((p, s), rep, delete=donate)
            if not donate:  # the trainer's update donates what it is given
                p, s = jax.tree_util.tree_map(jnp.copy, (p, s))
            return inner(p, s, jax.device_put(b, rows))
        return step

    def faulty_step(fault, pcfg, run, opt, batch):
        if fault == "no_sync":
            return make_step(pcfg, run, opt, strategy=NO_SYNC)
        if fault != "half_batch":
            return base_faulty(fault, pcfg, run, opt, batch)
        inner = make_step(pcfg, run, opt)
        half = jax.jit(lambda b: {k: jnp.concatenate([v[:batch // 2]] * 2)
                                  for k, v in b.items()})
        return lambda p, s, b: inner(p, s, half(b))

    base, base_faulty = train.make_step, train.faulty_step
    train.make_step, train.faulty_step = make_step, faulty_step
    try:
        yield
    finally:
        train.make_step, train.faulty_step = base, base_faulty


def measure(cell, *, seed, seconds, trace, t_start, fault=None):
    with data_parallel_steps(cell):
        return train.measure(cell, seed=seed, seconds=seconds, trace=trace,
                             t_start=t_start, fault=fault)
