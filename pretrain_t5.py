"""T5 pretraining CLI (reference pretrain_t5.py analog).

Span corruption over an indexed token corpus; sentinel tokens come from the
top of the vocabulary (the reference reserves them via --vocab_extra_ids):

    python pretrain_t5.py --model_name t5 --data_path corpus_text_document \
        --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt \
        --seq_length 512 --decoder_seq_length 128 --vocab_extra_ids 100 \
        --micro_batch_size 4 --global_batch_size 32 --train_iters 10000
"""

from __future__ import annotations

import jax

from megatron_llm_tpu.config import parse_args
from megatron_llm_tpu.models.t5 import init_t5_params, t5_loss_from_batch
from megatron_llm_tpu.training import pretrain
from megatron_llm_tpu.utils.platform import enable_compilation_cache


def extend_vocab_for_t5(cfg) -> None:
    """Reserve sentinel + bos/eos ids ABOVE the tokenizer vocabulary.

    The reference reserves sentinels via --vocab_extra_ids added to the
    tokenizer (tokenizer.py additional special tokens); here the model vocab
    is extended so sentinel ids never alias real corpus tokens. Must run
    before params are initialized.
    """
    assert cfg.model.vocab_size is not None, (
        "set --vocab_size (or a tokenizer that provides it) before T5 setup"
    )
    n_extra = cfg.data.vocab_extra_ids or 100
    cfg.data.vocab_extra_ids = n_extra
    # [base, base+n_extra) = sentinels; base+n_extra = bos; +1 = eos
    cfg.model.t5_base_vocab = cfg.model.vocab_size
    cfg.model.vocab_size += n_extra + 2


def t5_data_provider(cfg, tokenizer, consumed_samples):
    from megatron_llm_tpu.data.gpt_dataset import get_split_indexed_datasets
    from megatron_llm_tpu.data.samplers import build_pretraining_data_loader
    from megatron_llm_tpu.data.t5_dataset import T5Dataset

    splits = get_split_indexed_datasets(cfg.data.data_path, cfg.data.split)
    t = cfg.training
    base = getattr(cfg.model, "t5_base_vocab", None)
    assert base is not None, "call extend_vocab_for_t5(cfg) first"
    n_sent = cfg.data.vocab_extra_ids
    sentinel_ids = list(range(base, base + n_sent))

    # bos/eos always use the reserved slots (a tokenizer "eod" of 0 would
    # collide with pad); pad falls back to 0
    bos = base + n_sent
    eos = base + n_sent + 1
    try:
        pad = int(getattr(tokenizer, "pad", 0) or 0)
    except NotImplementedError:
        pad = 0
    dec_len = getattr(cfg.data, "decoder_seq_length", None) or max(
        cfg.data.seq_length // 4, 32
    )
    num_train = (t.train_iters or 0) * t.global_batch_size
    num_eval = t.eval_iters * t.global_batch_size * (
        1 + (t.train_iters or 0) // max(t.eval_interval, 1)
    )

    def make(ds, n):
        if ds is None or n == 0:
            return None
        return T5Dataset(
            ds, n, cfg.data.seq_length, dec_len, sentinel_ids,
            bos, eos, pad, seed=t.seed,
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
        argv = ["--model_name", "t5"] + argv
    enable_compilation_cache()
    cfg = parse_args(argv, n_devices=len(jax.devices()))
    if cfg.model.vocab_size is None:
        from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer

        build_tokenizer(cfg)  # sets cfg.model.vocab_size
    extend_vocab_for_t5(cfg)
    from megatron_llm_tpu.models.t5 import t5_pipeline_loss_fn

    result = pretrain(
        cfg,
        data_iterators_provider=t5_data_provider,
        params_provider=lambda key: init_t5_params(cfg, key),
        loss_fn=t5_loss_from_batch,
        pipeline_loss=t5_pipeline_loss_fn,
    )
    print(f"training done: {result['iteration']} iterations "
          f"({result['exit_reason']})")


if __name__ == "__main__":
    main()
