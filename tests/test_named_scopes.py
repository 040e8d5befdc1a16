"""Named scopes in the serve steps: every compiled step carries the
``attn.*``, ``mlp``, ``embed`` and ``head`` scopes in its ops' HLO
metadata, under its own module name, for latent (MLA) and grouped (GQA)
attention in each mode; and the scopes change metadata only — the
compiled decode step has the same ops and the same memory without them.
The tensor-parallel leg is in tests/test_serve_tp.py."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models.lm import init_cache, init_params
from repro.serve import make_jit_steps
from repro.steps import init_paged_slot_cache

pytestmark = pytest.mark.slow

SCOPES = ("attn.proj", "attn.kv_write", "attn.kv_gather", "attn.core",
          "mlp", "embed", "head")
SLOTS, PLEN, CACHE_LEN, PAGE, SPEC_W = 2, 8, 16, 8, 3


def scopes_in(hlo_text: str) -> set:
    """The known scopes named in the ops' ``op_name`` metadata."""
    found = set()
    for name in set(re.findall(r'op_name="([^"]*)"', hlo_text)):
        found.update(seg for seg in name.split("/") if seg in SCOPES)
    return found


def compiled(arch, step):
    """(module name, HLO text, compiled) of one serve step, tiny widths."""
    cfg = get(arch).tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kernel = step == "decode_kernel"
    steps = make_jit_steps(cfg, cache_len=CACHE_LEN, page_size=PAGE,
                           chunk=True, paged_kernel=kernel)
    dt = jnp.dtype(cfg.dtype)
    pool = init_paged_slot_cache(cfg, SLOTS, CACHE_LEN, dt, PAGE,
                                 SLOTS * CACHE_LEN // PAGE + 1)
    table = jnp.ones((SLOTS, CACHE_LEN // PAGE), jnp.int32)
    i32 = np.int32
    if step in ("decode", "decode_kernel"):
        low = steps["decode"].lower(params, pool,
                                    jnp.zeros((SLOTS, 1), i32),
                                    jnp.ones((SLOTS,), bool), table)
    elif step == "verify":
        low = steps["verify"].lower(params, pool,
                                    jnp.zeros((SLOTS, SPEC_W), i32),
                                    jnp.zeros((SLOTS,), i32),
                                    jnp.full((SLOTS,), SPEC_W, i32), table)
    elif step == "prefill":
        low = steps["prefill"].lower(params, jnp.zeros((1, PLEN), i32),
                                     None)
    else:
        rows = init_cache(cfg, 1, CACHE_LEN, dt)
        low = steps["chunk"].lower(params, rows, jnp.zeros((1, 4), i32),
                                   jnp.asarray(4, i32), None,
                                   attn_extent=PLEN, want_logits=True)
    comp = low.compile()
    txt = comp.as_text()
    return re.match(r"HloModule (\S+?),", txt).group(1), txt, comp


MODULES = {"decode": "jit_decode_step", "decode_kernel": "jit_decode_step",
           "verify": "jit_verify_step", "prefill": "jit_prefill_step",
           "chunk": "jit_chunk_step"}


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b"])
@pytest.mark.parametrize("step", list(MODULES))
def test_compiled_steps_carry_every_scope(arch, step):
    module, txt, _ = compiled(arch, step)
    assert module == MODULES[step]
    want = set(SCOPES)
    if step in ("decode_kernel", "prefill", "chunk"):
        want.discard("attn.kv_gather")      # no page gather on these legs
    assert scopes_in(txt) == want


def _program(comp):
    ops = [ln for ln in comp.as_text().splitlines()
           if re.match(r"\s+(ROOT )?%\S+ = ", ln)]
    m = comp.memory_analysis()
    return len(ops), (m.argument_size_in_bytes, m.output_size_in_bytes,
                      m.temp_size_in_bytes, m.alias_size_in_bytes)


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b"])
def test_scopes_change_metadata_only(arch, monkeypatch):
    _, txt, comp = compiled(arch, "decode")
    with_scopes = _program(comp)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare_txt, bare = compiled(arch, "decode")
    assert scopes_in(txt) and not scopes_in(bare_txt)
    assert _program(bare) == with_scopes
