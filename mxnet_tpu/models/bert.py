"""BERT (parity target: the reference's BERT fast path — fused attention
ops `src/operator/contrib/transformer.cc` driven from gluon; reference
config #3 "BERT-base pretraining, AMP bf16, fused attention via Pallas").

TPU-native design: attention is `npx.flash_attention` (the Pallas blockwise
kernel on TPU — O(L) memory, replacing the reference's O(L^2) interleaved
matmul + softmax chain); the whole encoder hybridizes into one XLA program;
bf16 compute via amp.convert_hybrid_block.  Long sequences shard over the
mesh with parallel.ring_attention.
"""
from __future__ import annotations

import math

from .. import autograd
from .. import numpy as np
from .. import numpy_extension as npx
from ..gluon import nn
from ..gluon.block import HybridBlock, _maybe_constrain
from ..gluon.parameter import Parameter
from ..ops.pallas.epilogue import fuse_epilogue_enabled


def _dense_nobias(dense, x):
    """Apply a Dense layer's matmul WITHOUT its bias — the bias is folded
    into the following fused epilogue (bias_gelu / bias_dropout_residual),
    mirroring the reference's transformer.cc fused fast path where the
    projection GEMM is bias-free and the epilogue kernel owns the add."""
    return npx.fully_connected(x, dense.weight.data(), None,
                               no_bias=True, flatten=False)

__all__ = ["BERTEncoder", "BERTModel", "bert_base", "bert_large", "bert_tiny"]


class MultiHeadAttention(HybridBlock):
    """Self-attention with fused QKV projection → flash attention."""

    def __init__(self, units, num_heads, dropout=0.0, use_flash=True):
        super().__init__()
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._dropout = dropout
        self._use_flash = use_flash
        self.qkv = nn.Dense(3 * units, flatten=False, in_units=units)
        self.proj = nn.Dense(units, flatten=False, in_units=units)

    def forward(self, x, mask=None):
        # x: (B, L, C)
        B, L, C = x.shape
        H, D = self._num_heads, self._head_dim
        qkv = self.qkv(x)  # (B, L, 3C)
        qkv = qkv.reshape(B, L, 3, H, D).transpose(2, 0, 3, 1, 4)  # (3,B,H,L,D)
        # split, not int-indexing: under symbolic tracing qkv[0] would be
        # output-selection (reference Symbol semantics), while np.split's
        # list works identically in eager and traced form
        parts = np.split(qkv, 3, axis=0)
        # under an active ShardingConfig, pin the heads layout: batch
        # over dp, heads over tp (SNIPPETS [1]'s q/k/v constraint in our
        # (B, H, L, D) layout) — GSPMD then keeps the whole attention
        # block head-parallel instead of re-gathering after the qkv GEMM
        q = _maybe_constrain(parts[0].squeeze(0), "attention")
        k = _maybe_constrain(parts[1].squeeze(0), "attention")
        v = _maybe_constrain(parts[2].squeeze(0), "attention")
        # the flash kernel covers attention-probability dropout (in-kernel
        # hash mask) and padding given as a (B,) valid-length vector; only
        # DENSE masks fall back to the unfused masked-softmax path
        valid_len = mask if (mask is not None and mask.ndim == 1) else None
        if self._use_flash and (mask is None or valid_len is not None):
            out = npx.flash_attention(q, k, v, dropout=self._dropout,
                                      kv_length=valid_len)  # (B,H,L,D)
        else:
            att = npx.batch_dot(q.reshape(B * H, L, D),
                                k.reshape(B * H, L, D),
                                transpose_b=True) / math.sqrt(D)
            if mask is not None:
                if valid_len is not None:  # (B,) lengths -> (B,1,1,L) keys
                    mask = (np.arange(L).reshape(1, 1, 1, L)
                            < valid_len.reshape(B, 1, 1, 1))
                att = att.reshape(B, H, L, L)
                att = npx.masked_softmax(att, mask, axis=-1)
                att = att.reshape(B * H, L, L)
            else:
                att = npx.softmax(att, axis=-1)
            if self._dropout:
                att = npx.dropout(att, p=self._dropout)
            out = npx.batch_dot(att, v.reshape(B * H, L, D)).reshape(B, H, L, D)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, C)
        if fuse_epilogue_enabled():
            # bias-free projection: TransformerLayer folds proj.bias into
            # the fused bias+dropout+residual epilogue
            return _dense_nobias(self.proj, out)
        return self.proj(out)


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu"):
        super().__init__()
        self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units)
        self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        self._activation = activation
        self._dropout = dropout

    def forward(self, x):
        if self._activation == "gelu" and fuse_epilogue_enabled():
            # fused bias+gelu after a bias-free GEMM; ffn2 also runs
            # bias-free — its bias joins TransformerLayer's fused
            # bias+dropout+residual epilogue
            h = npx.bias_gelu(_dense_nobias(self.ffn1, x),
                              self.ffn1.bias.data())
            if self._dropout:
                h = npx.dropout(h, p=self._dropout)
            return _dense_nobias(self.ffn2, h)
        h = npx.activation(self.ffn1(x), self._activation)
        if self._dropout:
            h = npx.dropout(h, p=self._dropout)
        return self.ffn2(h)


class TransformerLayer(HybridBlock):
    """Post-LN transformer encoder layer (BERT convention)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 use_flash=True):
        super().__init__()
        self.attention = MultiHeadAttention(units, num_heads, dropout,
                                            use_flash)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self._dropout = dropout

    def forward(self, x, mask=None):
        # token-stream constraint points: the residual stream stays
        # (B over dp, L over sp, C replicated) through both sublayers
        x = _maybe_constrain(x, "tokens")
        if fuse_epilogue_enabled():
            # attention/ffn return PRE-bias projections; each residual
            # join is one fused bias+dropout+residual kernel instead of
            # the add→dropout→add chain (three HBM round-trips)
            h = self.attention(x, mask)
            x = self.ln1(npx.bias_dropout_residual(
                h, self.attention.proj.bias.data(), x, p=self._dropout))
            h = self.ffn(x)
            return _maybe_constrain(self.ln2(npx.bias_dropout_residual(
                h, self.ffn.ffn2.bias.data(), x, p=self._dropout)), "tokens")
        h = self.attention(x, mask)
        if self._dropout:
            h = npx.dropout(h, p=self._dropout)
        x = self.ln1(x + h)
        h = self.ffn(x)
        if self._dropout:
            h = npx.dropout(h, p=self._dropout)
        return _maybe_constrain(self.ln2(x + h), "tokens")


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, max_length=512, use_flash=True):
        super().__init__()
        self._units = units
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerLayer(
                units, hidden_size, num_heads, dropout, use_flash))

    def forward(self, x, mask=None):
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT with MLM + NSP heads (pretraining configuration)."""

    def __init__(self, vocab_size=30522, num_layers=12, units=768,
                 hidden_size=3072, num_heads=12, dropout=0.1, max_length=512,
                 token_types=2, use_flash=True, tie_embeddings=True):
        super().__init__()
        self._units = units
        self._max_length = max_length
        self.word_embed = nn.Embedding(vocab_size, units)
        self.token_type_embed = nn.Embedding(token_types, units)
        self.position_embed = Parameter("position_embed",
                                        shape=(max_length, units))
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self._dropout = dropout
        self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                   dropout, max_length, use_flash)
        self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                               in_units=units)
        # MLM head
        self.mlm_dense = nn.Dense(units, flatten=False, in_units=units)
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        self._tie = tie_embeddings
        if not tie_embeddings:
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                        in_units=units)
        self.mlm_bias = Parameter("mlm_bias", shape=(vocab_size,))
        # NSP head
        self.nsp = nn.Dense(2, flatten=False, in_units=units)

    def forward(self, tokens, token_types=None, mask=None):
        B, L = tokens.shape
        x = self.word_embed(tokens)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = x + self.position_embed.data()[:L]
        x = self.embed_ln(x)
        if self._dropout:
            x = npx.dropout(x, p=self._dropout)
        seq = self.encoder(x, mask)  # (B, L, C)
        pooled = self.pooler(seq[:, 0])  # CLS
        # MLM logits over full sequence
        if fuse_epilogue_enabled():
            h = npx.bias_gelu(_dense_nobias(self.mlm_dense, seq),
                              self.mlm_dense.bias.data())
        else:
            h = npx.activation(self.mlm_dense(seq), "gelu")
        h = self.mlm_ln(h)
        if self._tie:
            # jnp.matmul broadcasts the leading batch dim of 1 — no (B,V,C)
            # materialization
            logits = npx.batch_dot(
                h, self.word_embed.weight.data().expand_dims(0),
                transpose_b=True) + self.mlm_bias.data()
        else:
            logits = self.mlm_decoder(h) + self.mlm_bias.data()
        nsp_logits = self.nsp(pooled)
        return logits, nsp_logits


def bert_base(vocab_size=30522, **kw):
    return BERTModel(vocab_size, num_layers=12, units=768, hidden_size=3072,
                     num_heads=12, **kw)


def bert_large(vocab_size=30522, **kw):
    return BERTModel(vocab_size, num_layers=24, units=1024, hidden_size=4096,
                     num_heads=16, **kw)


def bert_tiny(vocab_size=1000, **kw):
    kw.setdefault("max_length", 128)
    return BERTModel(vocab_size, num_layers=2, units=64, hidden_size=128,
                     num_heads=2, **kw)
