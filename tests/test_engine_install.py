"""The engine's cache install: each admission's batch-1 prefill cache goes
into its slot of the engine's cache in place, by one jitted program that
takes the cache donated and the slot traced.

For a dense (GQA K/V) and a latent-attention expert model at smoke width:
the caches after every admission are bit-identical to the eager
``big.at[:, i].set(one[:, 0])``, the program compiles once for all slots,
the cache it was handed is freed, and ``installs`` counts the admissions
that took a slot.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serve.engine import ServeEngine, install_slot
from repro.serve.scheduler import Request


def _eager_install(caches, one, slot):
    return jax.tree.map(
        lambda big, o: big.at[:, slot].set(o[:, 0].astype(big.dtype)),
        caches, one)


def _bits(tree):
    return [(x.dtype, x.shape, np.asarray(x).tobytes())
            for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("model", ["internlm2-1.8b", "deepseek-v2-236b"])
def test_install_writes_the_slot_in_place(model, rcfg_small, mesh1):
    eng = ServeEngine(get_smoke_config(model), rcfg_small, mesh1,
                      batch_slots=3, max_seq=32)
    prefilled = []            # (slot, prefill cache) of this admit call
    prefill, admit = eng._prefill, eng._admit

    def recording_prefill(params, tokens):
        logits, one = prefill(params, tokens)
        prefilled.append((eng._free_slot(), one))
        return logits, one

    slots_taken, compiled = [], []

    def checked_admit(now=None):
        before = eng.caches
        expect = jax.tree.map(lambda x: jnp.array(x, copy=True), before)
        prefilled.clear()
        admit(now)
        if not prefilled:
            return
        for slot, one in prefilled:
            expect = _eager_install(expect, one, slot)
        assert _bits(eng.caches) == _bits(expect)
        # the cache handed to the install was donated: it is gone, so no
        # second cache outlives an install
        assert all(x.is_deleted() for x in jax.tree.leaves(before))
        slots_taken.extend(slot for slot, _ in prefilled)
        compiled.append(install_slot._cache_size())

    eng._prefill, eng._admit = recording_prefill, checked_admit
    # three fill slots 0-2; the first finishes after one decode step and
    # the fourth refills its slot 0
    for i, (n, out) in enumerate(((5, 2), (9, 6), (3, 6), (7, 4))):
        eng.submit(Request(tenant_id=i % 2, prompt=list(range(1, n + 1)),
                           max_new_tokens=out, req_id=i))
    eng.run_until_drained()
    assert slots_taken == [0, 1, 2, 0]
    assert eng.installs == 4
    assert len(eng.completed) == 4
    # one program for every slot: the later installs compiled nothing
    assert len(set(compiled)) == 1
