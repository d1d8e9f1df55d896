"""The dense family: every layer RoPE grouped-query attention followed by a
SwiGLU MLP, as ``repro.models.lm`` builds a ``family="dense"``,
``mlp="swiglu"`` model.  A configuration file without a ``"family"`` key is
of this family.

A family module gives the harness four things, each from the
configuration file alone:

``program_cfg(conf)``
    the program's ``ModelCfg``, with the file's depth cut and every width
    checked against the file;
``make_weights(conf, seed)``
    seeded weights in the layout the program reads;
``make_reference(conf)``
    the plain float32 reference, ``logits(weights, seqs, n_prompts,
    served=, width=)``, importing nothing of the program;
``make_cost(conf, kv_format=None)``
    the least work of the algorithm: ``generate_cost(active, live_rows)``,
    ``attention_cost(live_rows)``, ``prompt_flops(n)`` and
    ``token_flops(keys)``.
"""
import dataclasses

import cost
import reference
import weights


def program_cfg(conf: dict):
    """The program's model config for ``conf``: the arch's published
    widths, with the depth cut the file states; every width is checked
    against the file."""
    from repro.configs import get_config
    m = conf["model"]
    cfg = dataclasses.replace(
        get_config(conf["arch"], smoke=conf.get("smoke", False)),
        n_layers=m["num_hidden_layers"], dtype_name=conf["serving"]["dtype"])
    want = {"d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "head_dim": m["head_dim"], "d_ff": m["intermediate_size"],
            "vocab": m["vocab_size"],
            "tie_embed": m["tie_word_embeddings"],
            "rope_theta": m["rope_theta"], "family": "dense",
            "mlp": "swiglu"}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['name']}: program config {got} != {want}")
    return cfg


make_weights = weights.make_weights
make_reference = reference.make_reference
make_cost = cost.Cost.from_config
