"""Pretrain a BERT biencoder on the Inverse Cloze Task.

Reference: /root/reference/pretrain_ict.py — builds the BiEncoder over ICT
data and trains with the in-batch contrastive loss (loss_func:76-118); the
retrieval accuracies print alongside the loss. The data path expects a
sentence-split indexed corpus (tools/preprocess_data.py --split_sentences)
and optionally a titles dataset (--titles_data_path).

    python pretrain_ict.py --data_path corpus_sent --titles_data_path titles \
        --num_layers 12 --hidden_size 768 --num_attention_heads 12 \
        --seq_length 256 --train_iters 10000 ...
"""

from __future__ import annotations

import jax

from megatron_llm_tpu.config import parse_args
from megatron_llm_tpu.data.ict_dataset import ICTDataset, ict_collator
from megatron_llm_tpu.data.indexed_dataset import make_dataset
from megatron_llm_tpu.data.samplers import build_pretraining_data_loader
from megatron_llm_tpu.retrieval.biencoder import (
    ict_loss_from_batch,
    init_biencoder_params,
)
from megatron_llm_tpu.training import pretrain
from megatron_llm_tpu.utils.platform import enable_compilation_cache


def _special_ids(tokenizer, vocab_size: int):
    def get(name, default):
        try:
            v = getattr(tokenizer, name, None)
            return int(v) if v is not None else default
        except NotImplementedError:
            return default

    return {
        "cls_id": get("cls", vocab_size - 4),
        "sep_id": get("sep", vocab_size - 3),
        "pad_id": get("pad", 0),
    }


def data_iterators_provider(cfg, tokenizer, consumed_samples):
    block_ds = make_dataset(cfg.data.data_path[0], cfg.data.data_impl)
    titles = None
    if cfg.retriever.titles_data_path:
        titles = make_dataset(cfg.retriever.titles_data_path, cfg.data.data_impl)
    ids = _special_ids(tokenizer, cfg.model.vocab_size)
    t = cfg.training

    num_train = max((t.train_iters or 0) * t.global_batch_size, 1)
    num_eval = max(t.eval_iters * t.global_batch_size, 1)

    def build(seed_offset, num_samples):
        return ICTDataset(
            block_ds, titles,
            max_seq_length=cfg.retriever.retriever_seq_length,
            query_in_block_prob=cfg.retriever.query_in_block_prob,
            seed=t.seed + seed_offset,
            use_titles=titles is not None,
            use_one_sent_docs=cfg.retriever.use_one_sent_docs,
            num_samples=num_samples,
            **ids,
        )

    def loader(ds, consumed):
        return build_pretraining_data_loader(
            ds, consumed, t.global_batch_size, cfg.data.dataloader_type,
            t.seed, collate_fn=ict_collator,
        )

    train_iter = loader(build(0, num_train), consumed_samples)
    valid_factory = lambda: loader(build(1, num_eval), 0)  # noqa: E731
    return train_iter, valid_factory


def main(argv=None):
    import sys

    argv = sys.argv[1:] if argv is None else argv
    if "--model_name" not in argv:
        argv = ["--model_name", "bert"] + argv
    enable_compilation_cache()
    cfg = parse_args(argv, n_devices=len(jax.devices()))
    # ICT trains the towers at retriever_seq_length
    cfg.data.seq_length = cfg.retriever.retriever_seq_length
    return pretrain(
        cfg,
        data_iterators_provider=data_iterators_provider,
        params_provider=lambda key: init_biencoder_params(cfg, key),
        loss_fn=ict_loss_from_batch,
    )


if __name__ == "__main__":
    main()
