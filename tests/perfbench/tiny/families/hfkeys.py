"""Family ``hfkeys``: a second family, for the tests alone. The zoo's own
transformer under another family's key names (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, an ``intermediate_size``
that is not four times the hidden size, ``max_position_embeddings``,
``layer_norm_eps``), leaves in the type ``param_dtype`` states, and one
shard's slice of the published vocabulary (``vocab_size // vocab_shards``
rows: what the traffic draws from and the logits cover).

Its block is GPT-2's, so everything that is the block's is
``perfbench/families/gpt2.py``'s, called with the keys translated; what is
this family's own are its keys, its checks and its slice. No file of
``perfbench/`` knows this family's name or its keys: a cell of it runs
through the same drivers and readers as a cell of ``gpt2``.
"""
import os

from perfbench.harness import manifest

GPT2 = manifest.load_family(os.path.join(manifest.BENCH_DIR, "families", "gpt2.py"))
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads")


def check_config(model, reduced):
    if model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("hidden_size is not a whole number of heads")
    if model["vocab_size"] % model["vocab_shards"]:
        raise ValueError("vocab_size does not divide over vocab_shards")
    for key in reduced:
        if key.endswith(("_dim", "_rank", "_size")) or key in WIDTHS:
            raise ValueError(f"`reduced` may never name a width: {key!r}")


def vocab_size(model):
    return model["vocab_size"] // model["vocab_shards"]


def _gpt2(model):
    return {"n_layer": model["num_hidden_layers"], "n_embd": model["hidden_size"],
            "n_head": model["num_attention_heads"], "n_inner": model["intermediate_size"],
            "n_positions": model["max_position_embeddings"],
            "vocab_size": vocab_size(model), "layer_norm_epsilon": model["layer_norm_eps"],
            "param_dtype": model["param_dtype"]}


def make_params(model, seed, shardings=None):
    return GPT2.make_params(_gpt2(model), seed, shardings)


def make_leaf(model, seed, path):
    return GPT2.make_leaf(_gpt2(model), seed, path)


def param_shapes(model):
    return GPT2.param_shapes(_gpt2(model))


def row_shardings(model, devices):
    return GPT2.row_shardings(_gpt2(model), devices)


def change_norms(model, seed, params):
    return GPT2.change_norms(_gpt2(model), seed, params)


def reference_params(model, seed):
    return make_params(model, seed)


def decode_model(model):
    return GPT2.decode_model(_gpt2(model))


def loss_fn(model, mix):
    return GPT2.loss_fn(_gpt2(model), mix)


def next_token_logits(params, tokens, model, precision):
    return GPT2.next_token_logits(params, tokens, _gpt2(model), precision)


logit_gaps = GPT2.logit_gaps


def adam_reference(params, batches, model, **kw):
    return GPT2.adam_reference(params, batches, _gpt2(model), **kw)


def train_flops_per_token(model, seq):
    return GPT2.train_flops_per_token(_gpt2(model), seq)


def prefill_flops(model, prompt):
    return GPT2.prefill_flops(_gpt2(model), prompt)


def decode_flops(model, context):
    return GPT2.decode_flops(_gpt2(model), context)


def kernel_work(kernel, model, facts):
    return GPT2.kernel_work(kernel, _gpt2(model), facts)
