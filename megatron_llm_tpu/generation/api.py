"""Inference API — megatron/text_generation/api.py analog.

``InferenceEngine`` bundles (cfg, params, tokenizer) — the state the
reference keeps in process-globals — and exposes the same surface:
``generate_and_post_process`` (api.py:19-68) and
``beam_search_and_post_process`` (api.py:152-178).  No parameter broadcasts
(api.py:93-117): SPMD means one controller process.

Compile-cache policy: prompt batches are padded UP to a BUCKET multiple and
the prefill is bucketed DOWN, so a server sees a handful of compilations,
then reuses them for any prompt mix.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.tokenization import (
    detokenize_generations,
    tokenize_prompts_and_batch,
)


def _bucket_down(n: int, bucket: int = gen.BUCKET) -> int:
    return max(1, (n // bucket) * bucket)


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class InferenceEngine:
    """Holds a model + tokenizer and serves generation requests."""

    def __init__(self, cfg, params, tokenizer):
        self.cfg = cfg
        if cfg.inference.int8_weights:
            if getattr(cfg.model, "fp8", None):
                raise ValueError(
                    "int8_weights and fp8 are mutually exclusive: the fp8 "
                    "linear path reads the unquantized 'kernel' leaves "
                    "(ops/fp8.py)")
            from megatron_llm_tpu.ops.quant import quantize_layer_weights_int8

            params = quantize_layer_weights_int8(params)
        self.params = params
        self.tokenizer = tokenizer

    def _check_limits(self, batch_size: int, samples_length: int,
                      run_length: Optional[int] = None) -> None:
        """Request-size guards (generation.py:133-138): position range on the
        logical length, token budget on the (bucket-padded) size that runs."""
        max_pos = self.cfg.model.max_position_embeddings
        if samples_length > max_pos:
            raise gen.InvalidRequest(
                "Length of prompt + tokens_to_generate longer than allowed")
        budget = self.cfg.inference.max_tokens_to_oom
        run_tokens = (run_length or samples_length) * batch_size
        if run_tokens > budget:
            raise gen.InvalidRequest(
                f"Too many tokens.  {run_tokens} is greater than {budget}")

    # -- generate ----------------------------------------------------------

    def generate(
        self,
        prompts: Sequence[str],
        tokens_to_generate: int = 0,
        return_output_log_probs: bool = False,
        top_k_sampling: int = 0,
        top_p_sampling: float = 0.0,
        temperature: float = 1.0,
        add_BOS: bool = False,
        use_eod_token_for_early_termination: bool = True,
        stop_on_double_eol: bool = False,
        stop_on_eol: bool = False,
        random_seed: int = -1,
    ):
        """api.generate analog (api.py:70-151): returns (tokens [b, S] np,
        lengths [b] np, output_log_probs [b, S-1] np or None)."""
        tok = self.tokenizer
        tokens, lengths, samples_length = tokenize_prompts_and_batch(
            tok, prompts, tokens_to_generate, add_BOS,
            pad_to_multiple=gen.BUCKET,
        )
        # pad the batch dim up to a power of two so the decode program is
        # compiled per size *bucket*, not per request size; padded rows are
        # copies of row 0 and are sliced off before returning.  The OOM
        # budget is checked against the padded size that actually runs.
        b = len(prompts)
        b_pad = _next_pow2(b)
        self._check_limits(b_pad, samples_length, tokens.shape[1])
        if b_pad != b:
            tokens = np.concatenate(
                [tokens, np.tile(tokens[:1], (b_pad - b, 1))], axis=0)
            lengths = np.concatenate(
                [lengths, np.tile(lengths[:1], b_pad - b)], axis=0)

        if tokens_to_generate == 0:
            # scoring mode (api.py:129-131): teacher-forced log-probs.
            # Score on the bucket-padded batch (stable compile cache) and
            # slice the result back to the true length.
            log_probs = np.asarray(gen.score_tokens(self.cfg, self.params, tokens))
            return (tokens[:b, :samples_length], lengths[:b],
                    log_probs[:b, : samples_length - 1])

        termination_id = getattr(self.cfg.model, "eos_id", None) or tok.eod
        prefill_len = min(_bucket_down(int(lengths.min())), tokens.shape[1] - 1)
        if random_seed == -1:
            # unseeded request: fresh entropy per call (the reference leaves
            # the torch RNG stream running, api.py:119-120)
            import os

            random_seed = int.from_bytes(os.urandom(4), "little")
        key = jax.random.PRNGKey(random_seed)
        result = gen.generate_tokens(
            self.cfg, self.params, tokens, lengths, samples_length,
            prefill_len=prefill_len, termination_id=termination_id,
            sample_key=key, top_k=top_k_sampling, top_p=top_p_sampling,
            temperature=temperature,
            use_eod_for_termination=use_eod_token_for_early_termination,
            stop_on_double_eol=stop_on_double_eol, stop_on_eol=stop_on_eol,
        )
        out_tokens = np.asarray(result.tokens)[:b, :samples_length]
        out_lengths = np.asarray(result.lengths)[:b]
        out_log_probs = (
            np.asarray(result.output_log_probs)[:b, : samples_length - 1]
            if return_output_log_probs else None
        )
        return out_tokens, out_lengths, out_log_probs

    def generate_and_post_process(
        self,
        prompts: Sequence[str],
        tokens_to_generate: int = 0,
        return_output_log_probs: bool = False,
        top_k_sampling: int = 0,
        top_p_sampling: float = 0.0,
        temperature: float = 1.0,
        add_BOS: bool = False,
        use_eod_token_for_early_termination: bool = True,
        stop_on_double_eol: bool = False,
        stop_on_eol: bool = False,
        random_seed: int = -1,
    ):
        """api.generate_and_post_process analog (api.py:19-68): returns
        (prompts_plus_generations, segments, output_log_probs, tokens)."""
        tokens, lengths, log_probs = self.generate(
            prompts, tokens_to_generate,
            return_output_log_probs=return_output_log_probs or tokens_to_generate == 0,
            top_k_sampling=top_k_sampling, top_p_sampling=top_p_sampling,
            temperature=temperature, add_BOS=add_BOS,
            use_eod_token_for_early_termination=use_eod_token_for_early_termination,
            stop_on_double_eol=stop_on_double_eol, stop_on_eol=stop_on_eol,
            random_seed=random_seed,
        )
        tokens, texts, segments = detokenize_generations(
            self.tokenizer, tokens, lengths, True)
        if return_output_log_probs and log_probs is not None:
            log_probs = [
                list(map(float, row[: len(seg) - 1]))
                for row, seg in zip(log_probs, segments)
            ]
        else:
            log_probs = None
        return texts, segments, log_probs, tokens

    # -- beam search -------------------------------------------------------

    def beam_search_and_post_process(
        self,
        prompts: Sequence[str],
        tokens_to_generate: int = 0,
        beam_size: int = 0,
        add_BOS: bool = False,
        stop_token: Optional[int] = None,
        num_return_gen: int = 1,
        length_penalty: float = 1.0,
    ):
        """api.beam_search_and_post_process analog (api.py:152-201)."""
        if len(prompts) != 1:
            raise gen.InvalidRequest(
                "beam search supports exactly one prompt")
        tok = self.tokenizer
        stop_token = tok.eod if stop_token is None else stop_token
        tokens, lengths, samples_length = tokenize_prompts_and_batch(
            tok, prompts, tokens_to_generate, add_BOS,
            pad_to_multiple=gen.BUCKET,
        )
        self._check_limits(1, samples_length, tokens.shape[1])
        out_tokens, scores = gen.beam_search(
            self.cfg, self.params, tokens[:1], int(lengths[0]),
            beam_size=beam_size, stop_token=stop_token,
            num_return_gen=num_return_gen, length_penalty=length_penalty,
            samples_length=samples_length,
        )
        out_tokens = np.asarray(out_tokens)[:, :samples_length]
        out_lengths = np.full((out_tokens.shape[0],), samples_length, np.int64)
        _, texts, segments = detokenize_generations(
            tok, out_tokens, out_lengths, True)
        return texts, segments, [float(s) for s in np.asarray(scores)]
