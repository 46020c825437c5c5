"""The jitted programs' device ops, named by layer.

`jax.named_scope` reaches the compiled HLO (`metadata={op_name="jit(f)/
transpose(jvp(decoder))/..."}`) but not the TPU v5e's profiler events, which
are named by the HLO instruction alone (`fusion.214`,
`pallas_bilinear_sample.16`). So the program keeps the map: whoever owns a
jitted program registers a function that returns its optimized HLO text,

    programs.register("_train_step_impl", text_fn)

and a reader of a device trace asks, after the run,

    programs.layers("_train_step_impl") -> {instruction name: layer}

`text_fn` runs only then (lazily, once): registering costs nothing, and a run
that never asks never lowers anything.

One table maps scope names to layers; the innermost mapped scope on an op's
name path is its layer, so `transpose(jvp(decoder))/...` is `decoder` and
`loss_pyramid/warp_composite_tgt_s1/...` is `render`. Stdlib only.
"""

from __future__ import annotations

import re
import threading
from typing import Callable, Dict, Optional

# scope-name prefix -> layer (a scope `render_src_s2` is matched by
# `render`). The layers partition a train step. MINE's: models/mpi.py opens
# `encoder` / `decoder`, train/loss.py `loss_pyramid`, `render`,
# `render_src_s<k>`, `warp_composite_tgt_s<k>`, `ssim_pairs_s<k>`,
# train/trainer.py `adam_update` / `nonfinite_guard`. The looped language
# model's: models/looplm.py opens `lm_embed`, `lm_attention`, `lm_mlp` (inside
# the scans' bodies: a scan is one `while` in the HLO, so the map names the
# ops inside it), train/lm_loss.py and the pass's final norm and gate
# `lm_head_loss`; Adam and the guard are `optimizer` for both families. The
# token model's serve step (models/moe_mla.py): `lm_mla_proj` (norms,
# projections, RoPE, W_o), `lm_mla_prefill`, `lm_mla_decode`, `lm_dense_mlp`,
# `lm_moe_router`, `lm_moe_experts`, `lm_moe_shared`, `lm_head` (`lm_head_loss`
# stands before it: the first prefix that matches names the layer); where the
# model selects keys or slides a window also `lm_dsa_index` (the indexer's
# projections and scores), `lm_dsa_select` (the exact top-k), `lm_dsa_prefill`
# / `lm_dsa_decode` (attention over the selected rows, a chunk's queries / the
# decode rows), `lm_swa_proj` / `lm_swa_prefill` / `lm_swa_decode` (a sliding
# layer's three parts), `lm_attn_gate`.
SCOPE_LAYERS = (
    ("encoder", "encoder"),
    ("decoder", "decoder"),
    ("render", "render"),
    ("warp_composite_tgt_s", "render"),
    ("loss_pyramid", "loss_pyramid"),
    ("ssim_pairs_s", "loss_pyramid"),
    ("adam_update", "optimizer"),
    ("nonfinite_guard", "optimizer"),
    ("lm_embed", "embed"),
    ("lm_attention", "attention"),
    ("lm_mlp", "mlp"),
    ("lm_head_loss", "head_loss"),
    ("lm_mla_proj", "mla_proj"),
    ("lm_mla_prefill", "mla_prefill"),
    ("lm_mla_decode", "mla_decode"),
    ("lm_dense_mlp", "dense_mlp"),
    ("lm_moe_router", "moe_router"),
    ("lm_moe_experts", "moe_experts"),
    ("lm_moe_shared", "moe_shared"),
    ("lm_head", "head"),
    # full layers under an indexer, sliding layers, the gate (PR 37)
    ("lm_dsa_index", "dsa_index"),
    ("lm_dsa_select", "dsa_select"),
    ("lm_dsa_prefill", "dsa_prefill"),
    ("lm_dsa_decode", "dsa_decode"),
    ("lm_swa_proj", "swa_proj"),
    ("lm_swa_prefill", "swa_prefill"),
    ("lm_swa_decode", "swa_decode"),
    ("lm_attn_gate", "attn_gate"),
)
# the layers that partition each model family's train step
FAMILY_LAYERS = {
    "mine": ("encoder", "decoder", "render", "loss_pyramid", "optimizer"),
    "looplm": ("embed", "attention", "mlp", "head_loss", "optimizer"),
    # the token model's serve step (serve/lm_engine.py)
    "moe_mla": ("embed", "mla_proj", "mla_prefill", "mla_decode",
                "dense_mlp", "moe_router", "moe_experts", "moe_shared",
                "head", "dsa_index", "dsa_select", "dsa_prefill",
                "dsa_decode", "swa_proj", "swa_prefill", "swa_decode",
                "attn_gate"),
}
LAYERS = tuple(dict.fromkeys(
    layer for layers in FAMILY_LAYERS.values() for layer in layers))

_IDENT = re.compile(r"[A-Za-z_]\w*")
# one instruction of an HLO module's text that carries an op_name:
#   %fusion.214 = bf16[...] fusion(...), ..., metadata={op_name="..." ...}
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = .*?'
    r'metadata=\{[^}]*?op_name="(?P<path>[^"]*)"')


def layer_of(path: str) -> Optional[str]:
    """The layer of one op from its name path (`op_name` of the HLO
    metadata): the innermost component whose scope name the table knows.
    A component may be wrapped by transformations (`transpose(jvp(x))`):
    its scope is the innermost identifier."""
    for component in reversed(path.split("/")):
        idents = _IDENT.findall(component)
        if not idents:
            continue
        for prefix, layer in SCOPE_LAYERS:
            if idents[-1].startswith(prefix):
                return layer
    return None


def layers_from_text(hlo_text: str) -> Dict[str, str]:
    """{instruction name: layer} for every instruction of an HLO module's
    text whose op_name path holds a mapped scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            layer = layer_of(m.group("path"))
            if layer is not None:
                out[m.group("name")] = layer
    return out


_lock = threading.Lock()
_text_fns: Dict[str, Callable[[], str]] = {}
_maps: Dict[str, Dict[str, str]] = {}


def register(name: str, text_fn: Callable[[], str]) -> None:
    """Make `name`'s op map available. `text_fn()` returns the program's
    optimized HLO text and is called at most once, by the first `layers`
    call; registering again (a new trainer) replaces it."""
    with _lock:
        _text_fns[name] = text_fn
        _maps.pop(name, None)


def registered(name: str) -> bool:
    with _lock:
        return name in _text_fns


def layers(name: str) -> Optional[Dict[str, str]]:
    """{instruction name: layer} of a registered program; None where no
    program of that name was registered."""
    with _lock:
        if name in _maps:
            return _maps[name]
        text_fn = _text_fns.get(name)
    if text_fn is None:
        return None
    found = layers_from_text(text_fn())
    with _lock:
        _maps[name] = found
    return found


def reset() -> None:
    """Tests only."""
    with _lock:
        _text_fns.clear()
        _maps.clear()
