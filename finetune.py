"""Main training CLI — pretraining, finetuning and instruction tuning of
GPT/Llama/Falcon/Mistral models (reference finetune.py analog).

Example:
    python finetune.py --model_name llama2 \
        --data_path /data/corpus_text_document \
        --tokenizer_type SentencePieceTokenizer --tokenizer_model tok.model \
        --seq_length 4096 --micro_batch_size 2 --global_batch_size 64 \
        --tensor_model_parallel_size 8 --pipeline_model_parallel_size 1 \
        --train_iters 1000 --lr 3e-5 --save ckpts --save_interval 200
"""

from __future__ import annotations

import jax

from megatron_llm_tpu.config import parse_args
from megatron_llm_tpu.models.families import validate_family
from megatron_llm_tpu.training import pretrain
from megatron_llm_tpu.utils.platform import enable_compilation_cache


def main():
    enable_compilation_cache()
    cfg = parse_args(n_devices=len(jax.devices()))
    validate_family(cfg)
    if cfg.checkpoint.use_checkpoint_args and cfg.checkpoint.load:
        from megatron_llm_tpu.checkpointing import load_args_from_checkpoint

        load_args_from_checkpoint(cfg, cfg.checkpoint.load)
    result = pretrain(cfg)
    print(f"training done: {result['iteration']} iterations "
          f"({result['exit_reason']})")


if __name__ == "__main__":
    main()
