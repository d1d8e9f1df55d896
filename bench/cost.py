"""Operations and bytes that the algorithm needs, from a configuration's
shapes and the storage formats it declares.

What is counted is the least work of the algorithm, not what the compiled
program happens to do: the weights at their declared format (posit8 = 1 B,
posit16 = 2 B an element), only the live K/V rows of each active slot
(prompt plus the tokens emitted so far; the pages a kernel walks past the
end and the lane padding do not count), and no bucket padding or in-graph
fake-quantization.  So a change that removes wasted work raises a roofline
share without carrying it past 100%.
"""
from __future__ import annotations

import dataclasses

FORMAT_BYTES = {"posit4_1": 0.5, "posit8_2": 1.0, "posit16_2": 2.0,
                "bfloat16": 2.0, "float32": 4.0}
KV_CODE_BYTES = {"posit4": 0.5, "posit8": 1.0, "posit16": 2.0,
                 "bf16": 2.0, "f32": 4.0}
KV_SCALE_BYTES = {"posit4": 4.0, "posit8": 4.0, "posit16": 4.0,
                  "bf16": 0.0, "f32": 0.0}


@dataclasses.dataclass(frozen=True)
class Cost:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    w_attn: float
    w_mlp: float
    w_embed: float
    w_head: float
    kv_code: float
    kv_scale: float

    @classmethod
    def from_config(cls, conf: dict, kv_format: str = None) -> "Cost":
        m, s = conf["model"], conf["serving"]
        wf = s["weight_formats"]
        kv = kv_format or s["kv_format"]
        return cls(m["num_hidden_layers"], m["hidden_size"],
                   m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"], m["intermediate_size"], m["vocab_size"],
                   bool(m["tie_word_embeddings"]),
                   FORMAT_BYTES[wf["attn"]], FORMAT_BYTES[wf["mlp"]],
                   FORMAT_BYTES[wf["embed"]], FORMAT_BYTES[wf["head"]],
                   KV_CODE_BYTES[kv], KV_SCALE_BYTES[kv])

    # ---- parameters ----
    @property
    def attn_params(self) -> int:
        """Per layer: q, k, v and output projections."""
        return (self.d * (self.heads + 2 * self.kv_heads) * self.head_dim
                + self.heads * self.head_dim * self.d)

    @property
    def mlp_params(self) -> int:
        """Per layer: gate and up (d x 2ff) and down (ff x d)."""
        return 3 * self.d * self.ff

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def matmul_params(self) -> int:
        """Parameters a token multiplies by: every layer and the head."""
        return self.layers * (self.attn_params + self.mlp_params) \
            + self.head_params

    @property
    def params(self) -> int:
        """Matrix and embedding parameters (norm vectors left out)."""
        emb = self.vocab * self.d
        return self.layers * (self.attn_params + self.mlp_params) + emb \
            + (0 if self.tied else self.head_params)

    # ---- operations ----
    def attn_flops_per_key(self) -> int:
        """One query against one key, all heads, one layer: q.k and p.v."""
        return 4 * self.heads * self.head_dim

    def token_flops(self, keys: int) -> float:
        """One token through the model, attending to ``keys`` keys (its
        own included) in every layer."""
        return 2.0 * self.matmul_params \
            + self.layers * self.attn_flops_per_key() * keys

    def prompt_flops(self, n: int) -> float:
        """A prompt of ``n`` tokens, causal: token i attends to i + 1
        keys.  The head runs on the last position only, as a prefill's
        next-token logits need."""
        body = self.matmul_params - self.head_params
        return (2.0 * body * n + 2.0 * self.head_params
                + self.layers * self.attn_flops_per_key() * n * (n + 1) / 2)

    # ---- bytes ----
    @property
    def weight_bytes(self) -> float:
        """Weights one decode step must read, at their declared formats
        (the head once; the embedding rows are counted per token)."""
        return (self.layers * (self.attn_params * self.w_attn
                               + self.mlp_params * self.w_mlp)
                + self.head_params * self.w_head)

    @property
    def kv_row_bytes(self) -> float:
        """One token's K and V in one layer: codes plus per-head scales."""
        return 2.0 * self.kv_heads * (self.head_dim * self.kv_code
                                      + self.kv_scale)

    def generate_cost(self, active: int, live_rows: int):
        """(flops, bytes) of one decode step for ``active`` slots whose
        live K/V rows (each slot's position + 1) sum to ``live_rows``."""
        flops = 2.0 * self.matmul_params * active \
            + self.layers * self.attn_flops_per_key() * live_rows
        nbytes = (self.weight_bytes + active * self.d * self.w_embed
                  + self.layers * self.kv_row_bytes * (live_rows + active))
        return flops, nbytes

    def attention_cost(self, live_rows: int):
        """(flops, bytes) of one paged decode-attention call (one layer)
        over ``live_rows`` live K/V rows summed over the active slots."""
        return (float(self.attn_flops_per_key() * live_rows),
                self.kv_row_bytes * live_rows)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute at peak
    and bytes at full bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
