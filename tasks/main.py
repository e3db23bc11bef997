"""Downstream-task harness dispatcher (reference tasks/main.py:14-94).

    python tasks/main.py --task MNLI  --train_data train.tsv --valid_data dev.tsv ...
    python tasks/main.py --task RACE  --train_data RACE/train ...
    python tasks/main.py --task WIKITEXT103 --valid_data wiki.test.tokens --load ckpt
    python tasks/main.py --task LAMBADA --valid_data lambada.jsonl --load ckpt
"""

from __future__ import annotations

import os
import sys

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from megatron_llm_tpu.config import parse_args
from megatron_llm_tpu.utils.platform import enable_compilation_cache


def get_tasks_args(parser):
    group = parser.add_argument_group("tasks")
    group.add_argument("--task", type=str, required=True,
                       help="MNLI|QQP|RACE|WIKITEXT103|LAMBADA|ORQA|"
                            "ORQA-FINETUNE|MSDP-PROMPT|MSDP-EVAL-F1")
    group.add_argument("--train_data", type=str, default=None)
    group.add_argument("--valid_data", type=str, default=None)
    group.add_argument("--epochs", type=int, default=3)
    group.add_argument("--strict_lambada", action="store_true")
    # ORQA (reference tasks/orqa/evaluate_orqa.py surface)
    group.add_argument("--qa_data", type=str, default=None,
                       help="jsonl {question, answers} for ORQA")
    group.add_argument("--evidence_data", type=str, default=None,
                       help="evidence for ORQA: jsonl {id, text, title} "
                            "or DPR psgs_w100-style tsv")
    group.add_argument("--report_topk", type=int, default=20)
    group.add_argument("--match", type=str, default="string",
                       choices=["string", "regex"])
    # MSDP (reference tasks/msdp/main.py surface)
    group.add_argument("--prompt_file", type=str, default=None)
    group.add_argument("--prompt_type", type=str, default="knowledge",
                       choices=["knowledge", "response"])
    group.add_argument("--sample_input_file", type=str, default=None)
    group.add_argument("--sample_output_file", type=str, default=None)
    group.add_argument("--num_prompt_examples", type=int, default=10)
    group.add_argument("--out_seq_length", type=int, default=64)
    group.add_argument("--knowledge_file", type=str, default=None,
                       help="stage-1 output to condition stage 2 on "
                            "(omit for oracle-knowledge evaluation)")
    group.add_argument("--guess_file", type=str, default=None)
    group.add_argument("--answer_file", type=str, default=None)
    return parser


def _special_ids(tokenizer, vocab_size: int):
    """cls/sep/pad ids with top-of-vocab fallbacks for tokenizers without
    BERT specials (pretrain_bert.py convention)."""

    def get(name, default):
        try:
            v = getattr(tokenizer, name, None)
            return int(v) if v is not None else default
        except NotImplementedError:
            return default

    return dict(
        cls_id=get("cls", vocab_size - 4),
        sep_id=get("sep", vocab_size - 3),
        pad_id=get("pad", 0),
    )


def _load_params_for_eval(cfg, init_fn=None):
    """Initialize + load checkpoint params (zero-shot / eval paths)."""
    from megatron_llm_tpu.checkpointing import load_checkpoint
    from megatron_llm_tpu.core.parallel_state import (
        build_mesh_from_config,
        global_mesh,
    )
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.parallel.tp import param_shardings

    if init_fn is None:
        init_fn = init_model_params
    mesh = build_mesh_from_config(cfg)
    with global_mesh(mesh):
        params = init_fn(cfg, jax.random.PRNGKey(0))
        if cfg.checkpoint.load:
            shard = param_shardings(mesh, params)
            params, *_ = load_checkpoint(
                cfg, cfg.checkpoint.load, params, None, shard, None
            )
    return mesh, params


def run_zeroshot(cfg, extra):
    from megatron_llm_tpu.core.parallel_state import global_mesh
    from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
    from tasks.zeroshot_gpt.evaluate import (
        evaluate_lambada,
        evaluate_wikitext_ppl,
        load_lambada_jsonl,
    )

    tokenizer = build_tokenizer(cfg)
    mesh, params = _load_params_for_eval(cfg)
    with global_mesh(mesh):
        if cfg.inference.int8_weights:
            # weight-only int8 zeroshot eval (ops/quant.py): the e2e
            # quality gate for the decode-path quantization —
            # `--int8_weights` on the same checkpoint measures the ppl
            # delta vs the full-precision run (round-4 VERDICT item 5)
            if cfg.model.fp8:
                raise ValueError(  # same guard as generation/api.py
                    "--int8_weights and fp8 are mutually exclusive: the "
                    "fp8 GEMM path reads the unquantized kernel leaves")
            from megatron_llm_tpu.ops.quant import quantize_layer_weights_int8

            params = quantize_layer_weights_int8(params)
        if extra.task == "WIKITEXT103":
            with open(extra.valid_data) as f:
                text = f.read()
            num_original = len(text.split())
            tokens = tokenizer.tokenize(text)
            result = evaluate_wikitext_ppl(
                cfg, params, tokens, num_original_tokens=num_original
            )
        else:  # LAMBADA
            samples = load_lambada_jsonl(extra.valid_data, tokenizer.tokenize)
            result = evaluate_lambada(
                cfg, params, samples, strict=extra.strict_lambada
            )
    print({extra.task: result})
    return result


def _run_finetune(cfg, extra, dataset_cls, read_records, num_classes):
    """Shared GLUE/RACE flow: tokenizer -> datasets -> epochs -> finetune."""
    from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
    from tasks.finetune_utils import finetune_classification

    tokenizer = build_tokenizer(cfg)
    ids = _special_ids(tokenizer, cfg.model.vocab_size)

    def make(path):
        if not path:
            return None
        return dataset_cls(
            read_records(path), tokenizer.tokenize, cfg.data.seq_length, **ids
        )

    train_ds = make(extra.train_data)
    valid_ds = make(extra.valid_data)
    if cfg.training.train_iters is None:
        cfg.training.train_iters = max(
            1, extra.epochs * len(train_ds) // cfg.training.global_batch_size
        )
    return finetune_classification(cfg, train_ds, valid_ds, num_classes)


def run_glue(cfg, extra):
    from tasks.finetune_utils import ClassificationDataset
    from tasks.glue.data import PROCESSORS

    proc = PROCESSORS[extra.task]()
    return _run_finetune(
        cfg, extra, ClassificationDataset, proc.records, proc.num_classes
    )


def run_race(cfg, extra):
    from tasks.finetune_utils import MultipleChoiceDataset
    from tasks.race.data import read_race_records

    # multiple choice scores each option with a 1-logit head
    return _run_finetune(
        cfg, extra, MultipleChoiceDataset, read_race_records, num_classes=1
    )


def run_orqa(cfg, extra):
    """Unsupervised NQ-style retrieval accuracy (tasks/orqa/evaluate_orqa.py)."""
    import numpy as np

    from megatron_llm_tpu.core.parallel_state import global_mesh
    from megatron_llm_tpu.retrieval.biencoder import init_biencoder_params
    from megatron_llm_tpu.retrieval.index import BlockEmbedStore
    from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
    from tasks.orqa.evaluate import ORQAEvaluator

    tokenizer = build_tokenizer(cfg)
    ids = _special_ids(tokenizer, cfg.model.vocab_size)
    seq = cfg.retriever.retriever_seq_length

    def tokenize(question):
        body = tokenizer.tokenize(question)[: seq - 2]
        toks = np.zeros((seq,), np.int64)
        row = [ids["cls_id"], *body, ids["sep_id"]]
        toks[: len(row)] = row
        mask = (np.arange(seq) < len(row)).astype(np.int64)
        return toks, mask

    for flag, value in (("qa_data", extra.qa_data),
                        ("evidence_data", extra.evidence_data)):
        if not value:
            raise SystemExit(f"--task ORQA requires --{flag}")
    if not cfg.retriever.embedding_path:
        raise SystemExit("--task ORQA requires --embedding_path "
                         "(a BlockEmbedStore built by retrieval.indexer)")

    mesh, params = _load_params_for_eval(cfg, init_fn=init_biencoder_params)
    with global_mesh(mesh):
        store = BlockEmbedStore(cfg.retriever.embedding_path,
                                load_from_path=True)
        ev = ORQAEvaluator(cfg, params, store, tokenize)
        return ev.evaluate(extra.qa_data, extra.evidence_data,
                           top_k=extra.report_topk, match_type=extra.match)


def run_orqa_finetune(cfg, extra):
    """Supervised DPR-style retriever finetuning (tasks/orqa/supervised)."""
    from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
    from tasks.orqa.supervised import (
        OpenRetrievalSupervisedDataset,
        finetune_orqa,
        load_dpr_json,
    )

    if not extra.train_data:
        raise SystemExit("--task ORQA-FINETUNE requires --train_data "
                         "(DPR-format json)")
    tokenizer = build_tokenizer(cfg)
    ids = _special_ids(tokenizer, cfg.model.vocab_size)
    t = cfg.training
    seq = cfg.retriever.retriever_seq_length

    records = load_dpr_json(extra.train_data)
    if t.train_iters is None:  # derive from --epochs like the GLUE path
        t.train_iters = max(
            1, extra.epochs * len(records) // t.global_batch_size
        )

    def make(path, n, recs=None):
        if not path and recs is None:
            return None
        return OpenRetrievalSupervisedDataset(
            recs if recs is not None else load_dpr_json(path),
            tokenizer.tokenize, seq, seed=t.seed, num_samples=n, **ids,
        )

    train_ds = make(None, max(t.train_iters * t.global_batch_size, 1),
                    recs=records)
    valid_ds = make(extra.valid_data,
                    max(t.eval_iters * t.global_batch_size, 1))
    return finetune_orqa(cfg, train_ds, valid_ds)


def run_msdp_prompt(cfg, extra):
    """Knowledge/response generation stage (tasks/msdp/prompt.py)."""
    from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer
    from tasks.msdp.prompt import generate_samples, make_local_generate_fn

    for flag in ("prompt_file", "sample_input_file", "sample_output_file"):
        if not getattr(extra, flag):
            raise SystemExit(f"--task MSDP-PROMPT requires --{flag}")
    out_dir = os.path.dirname(os.path.abspath(extra.sample_output_file))
    os.makedirs(out_dir, exist_ok=True)

    tokenizer = build_tokenizer(cfg)
    mesh, params = _load_params_for_eval(cfg)
    from megatron_llm_tpu.core.parallel_state import global_mesh

    with global_mesh(mesh):
        fn = make_local_generate_fn(cfg, params, tokenizer)
        n = generate_samples(
            fn, extra.prompt_file, extra.prompt_type,
            extra.sample_input_file, extra.sample_output_file,
            n_prompt_examples=extra.num_prompt_examples,
            out_seq_length=extra.out_seq_length,
            knowledge_file=extra.knowledge_file,
        )
    print(f"generated {n} samples -> {extra.sample_output_file}")
    return n


def main():
    import argparse

    # pull the task args off argv, pass the rest to the standard parser
    task_parser = get_tasks_args(argparse.ArgumentParser(allow_abbrev=False))
    extra, rest = task_parser.parse_known_args()

    if extra.task == "MSDP-EVAL-F1":  # pure text metric, no model/config
        from tasks.msdp.evaluate import evaluate_f1

        if not extra.guess_file or not extra.answer_file:
            raise SystemExit(
                "--task MSDP-EVAL-F1 requires --guess_file and --answer_file")
        return evaluate_f1(extra.guess_file, extra.answer_file)

    enable_compilation_cache()
    cfg = parse_args(rest, n_devices=len(jax.devices()))

    if extra.task in ("WIKITEXT103", "LAMBADA"):
        return run_zeroshot(cfg, extra)
    if extra.task in ("MNLI", "QQP"):
        return run_glue(cfg, extra)
    if extra.task == "RACE":
        return run_race(cfg, extra)
    if extra.task == "ORQA":
        return run_orqa(cfg, extra)
    if extra.task == "ORQA-FINETUNE":
        return run_orqa_finetune(cfg, extra)
    if extra.task == "MSDP-PROMPT":
        return run_msdp_prompt(cfg, extra)
    raise ValueError(f"unknown task {extra.task}")


if __name__ == "__main__":
    main()
