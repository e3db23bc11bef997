"""BERT pretraining CLI (reference pretrain_bert.py analog).

Masked-LM + sentence-order binary head over an indexed token corpus:

    python pretrain_bert.py --model_name bert --data_path corpus_text_document \
        --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt \
        --seq_length 512 --micro_batch_size 4 --global_batch_size 32 \
        --train_iters 10000 --lr 1e-4
"""

from __future__ import annotations

import jax

from megatron_llm_tpu.config import parse_args
from megatron_llm_tpu.models.bert import bert_loss_from_batch, init_bert_params
from megatron_llm_tpu.training import pretrain
from megatron_llm_tpu.utils.platform import enable_compilation_cache


def _special_ids(tokenizer, vocab_size: int):
    """cls/sep/mask/pad ids from the tokenizer, with top-of-vocab fallbacks
    for tokenizers without BERT specials (e.g. NullTokenizer in tests)."""

    def get(name, default):
        try:
            v = getattr(tokenizer, name, None)
            return int(v) if v is not None else default
        except NotImplementedError:
            return default

    return {
        "cls_id": get("cls", vocab_size - 4),
        "sep_id": get("sep", vocab_size - 3),
        "mask_id": get("mask", vocab_size - 2),
        "pad_id": get("pad", 0),
    }


def bert_data_provider(cfg, tokenizer, consumed_samples):
    from megatron_llm_tpu.data.bert_dataset import BertDataset
    from megatron_llm_tpu.data.gpt_dataset import get_split_indexed_datasets
    from megatron_llm_tpu.data.samplers import build_pretraining_data_loader

    splits = get_split_indexed_datasets(cfg.data.data_path, cfg.data.split)
    ids = _special_ids(tokenizer, cfg.model.vocab_size)
    t = cfg.training
    num_train = (t.train_iters or 0) * t.global_batch_size
    num_eval = t.eval_iters * t.global_batch_size * (
        1 + (t.train_iters or 0) // max(t.eval_interval, 1)
    )

    def make(ds, n):
        if ds is None or n == 0:
            return None
        return BertDataset(
            ds, n, cfg.data.seq_length, cfg.model.vocab_size,
            seed=t.seed, masked_lm_prob=0.15,
            binary_head=cfg.model.bert_binary_head, **ids,
        )

    train_ds = make(splits[0], max(num_train, 1))
    valid_ds = make(splits[1], max(num_eval, 1))
    train_iter = build_pretraining_data_loader(
        train_ds, consumed_samples, t.global_batch_size,
        cfg.data.dataloader_type, t.seed,
    )
    valid_factory = (
        (lambda: build_pretraining_data_loader(
            valid_ds, 0, t.global_batch_size, cfg.data.dataloader_type, t.seed
        )) if valid_ds else None
    )
    return train_iter, valid_factory


def main():
    import sys

    argv = sys.argv[1:]
    if "--model_name" not in argv:
        argv = ["--model_name", "bert"] + argv
    enable_compilation_cache()
    cfg = parse_args(argv, n_devices=len(jax.devices()))
    from megatron_llm_tpu.models.bert import bert_pipeline_hooks

    result = pretrain(
        cfg,
        data_iterators_provider=bert_data_provider,
        params_provider=lambda key: init_bert_params(cfg, key),
        loss_fn=bert_loss_from_batch,
        pipeline_hooks=bert_pipeline_hooks,
    )
    print(f"training done: {result['iteration']} iterations "
          f"({result['exit_reason']})")


if __name__ == "__main__":
    main()
