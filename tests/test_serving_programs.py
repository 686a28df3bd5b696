"""What every serve configuration's tiny engine compiles and what its spans
say, pinned: the sha256 of each executable's lowered text, and the non-time
attributes of `serving::prefill`, `serving::decode_step`,
`serving::decode.wait` and `serving::step` over one request of one prefill
and three decode calls. The engines are built from `benchmark/configs/*.json`
at their `tiny` sizes, as the benchmark builds them. A change to the serving
path that means to leave the programs and the spans as they are shows here
that it did; one that means to change them takes new values and says why."""
import hashlib
import importlib
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import tiny_of                              # noqa: E402
from paddle_tpu import profiler                                # noqa: E402
from paddle_tpu.observability.flight_recorder import SpanLog   # noqa: E402
from paddle_tpu.serving import (PagedEngineConfig,             # noqa: E402
                                PagedGenerationEngine, Scheduler,
                                ServingConfig, blocks)

CONFIGS = os.path.join(ROOT, "benchmark", "configs")
GPT = "cerebras_gpt_1p3b"
HYBRID = ("ling3_flash_ep4_share", "deepseek_v3_ep16_share",
          "nemotron3_nano_ep8_share", "evabyte_6p5b_pp4_stage",
          "keye_vl2_a3b_ep8_share")
# a case: (configuration, what is changed of its engine). GPT three ways:
# its own engine (the gather arm off the TPU); its decode traced under the
# decode kernel (interpreted here); and int8 pools
CASES = [(name, None) for name in HYBRID] + [
    (GPT, None), (GPT, "decode_kernel"), (GPT, "int8")]


def case_id(case):
    name, variant = case
    return name if variant is None else f"{name}-{variant}"


def full_config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def tiny_engine(name, variant=None):
    """The tiny engine of the configuration `name`, its model built with
    the benchmark's weights for seed 5."""
    config = tiny_of(full_config(name))
    if name == GPT:
        from benchmark.loops.closed_loop import build_model
        model = build_model(config, 5)
    else:
        from paddle_tpu.text.models.hybrid import HybridConfig, HybridDecoder
        model = HybridDecoder(HybridConfig(
            **config["program"]["model_config"]))
        model.eval()
        weights = importlib.import_module(
            f"benchmark.harness.{config['harness']['weights']}")
        model.load_arrays(weights.named(config, 5, config["dtype"]["param"]))
    kw = dict(config["program"]["paged_engine_config"])
    if variant == "int8":
        kw["kv_dtype"] = "int8"
    return PagedGenerationEngine(model, PagedEngineConfig(**kw))


def lowered_texts(name, variant=None):
    """({executable: its lowered text}, the engine) of the tiny engine of
    the configuration `name`."""
    eng = tiny_engine(name, variant)
    impl = "decode_kernel" if variant == "decode_kernel" \
        else eng.attention_impl
    with blocks.attention_impl(impl):
        out = {"decode": jax.jit(eng._decode_fn).lower(
            *eng._decode_args()).as_text()}
        for b in eng.config.prefill_buckets:
            out[f"prefill[{b}]"] = jax.jit(
                eng._make_prefill(b)._fn).lower(*eng._prefill_args(
                    b, 0, np.zeros((b,), np.int32), 1, 0)).as_text()
    return out, eng


def lowered_programs(name, variant=None):
    """{executable: sha256 of its lowered text}."""
    return {k: hashlib.sha256(text.encode()).hexdigest()
            for k, text in lowered_texts(name, variant)[0].items()}


# sha256 of each lowered executable, taken on the parent of the change that
# gave GPT a cache layout and the paged engine one decode and one prefill
# body (59c2c58). The five hybrid configurations' programs and GPT's decode
# programs are the parent's byte for byte. GPT's prefills were taken anew
# there: the one prefill body returns the first token as the [1] array the
# sampler gives (behind it the counters of a model that has any), where
# GPT's returned it as a scalar; the slice and reshape that made the scalar
# are all that went.
PROGRAMS = {
    'ling3_flash_ep4_share': {
        'decode':
            "ceee6759b3ba86d52fe7ebc951c5d1a7"
            "05711cd97c2d4de2f181ff3bf49d726c",
        'prefill[32]':
            "e377f3f9fed6a5089023034f9d525ef8"
            "2480fa325829d2d74716fd31ec16f419",
        'prefill[64]':
            "72c0e45f9ae7fc6b79e1056a696fec4e"
            "b930afa3db1ee986fd3fbbfb5622838f"},
    'deepseek_v3_ep16_share': {
        'decode':
            "a7de72a723f4fe21b93bf8e50aea66a3"
            "91991fbe4f429e12ec3bc946ce008163",
        'prefill[32]':
            "bd370268824ad12fd23fd5674b9ed659"
            "02647910342c841e0a3dcf7e4cd75b62",
        'prefill[64]':
            "4a18a719e2fd39aca597b5a279d144e4"
            "e7610affb5d676946763926806bef6e6"},
    'nemotron3_nano_ep8_share': {
        'decode':
            "5893e3ec35b54f45ca234965903979c2"
            "3b59263421fc120263be919a9b46b778",
        'prefill[32]':
            "93ab8dc23ce96e52c0302a3b7a2c043a"
            "5e026b0acb71078a8682aa26bf45309a",
        'prefill[64]':
            "9dd6aee7d44853dfab7a669088c0b099"
            "5377f21f31dddbb8411cc528dd43a643"},
    'evabyte_6p5b_pp4_stage': {
        'decode':
            "273f33cafcf4c5c010bda907d0ac6939"
            "2e6ab0f4750f541a73eb3ad643c8d66d",
        'prefill[32]':
            "125e5c235a6c3767edc6ddd0dde164fb"
            "722eda9e2e6371c2ac58f3a5559e2103",
        'prefill[64]':
            "1c01dbd751c711f3bf78adf7eb72ff7b"
            "15490ab415f5fc13adcd506240912771"},
    'keye_vl2_a3b_ep8_share': {
        'decode':
            "3f3c2d3624f22a142a6c4bce753e7232"
            "e8a4e161eea0eb9f563199eb08beaa36",
        'prefill[32]':
            "dbed745b237a9e3bc97d8b66c2594ab0"
            "be8b3da042bbc672998b697ab477cd14",
        'prefill[64]':
            "5a73f4114bc4f682e966014befa10448"
            "f0affbc9e22c1017c1ebe6e797363221"},
    'cerebras_gpt_1p3b': {
        'decode':
            "e8ff45a3c49b832b8c1bd4dd0717210f"
            "92b41aa1a5d281e08461f4850547a702",
        'prefill[32]':
            "d5c2827dd4cc20dda331e2b03651a5b3"
            "7e0c0d4eb045ab6154044a97ff0cf81c",
        'prefill[64]':
            "85a63e36f5296493ebc29d11539b1701"
            "e972cd29dfb40c3a21aea8ec9de5c545"},
    'cerebras_gpt_1p3b-decode_kernel': {
        'decode':
            "3f1900cb6f962d51f119bdfaaa48bf0b"
            "bac701e7e7584d49c74eb02b58cadeda",
        'prefill[32]':
            "d5c2827dd4cc20dda331e2b03651a5b3"
            "7e0c0d4eb045ab6154044a97ff0cf81c",
        'prefill[64]':
            "85a63e36f5296493ebc29d11539b1701"
            "e972cd29dfb40c3a21aea8ec9de5c545"},
    'cerebras_gpt_1p3b-int8': {
        'decode':
            "4fcdadb5247f73ef9e96dcdbcb338477"
            "c21a65026479ec55f4930a2c02f4ff87",
        'prefill[32]':
            "5615db83174b1277b5c697bf7a65ee6d"
            "7a57792090574991d8fe5e6172b15bf8",
        'prefill[64]':
            "a9a203e1e4fe72b5e2912425f46d6b64"
            "2a86bc9d3b3c64d2e4007912b99c5d48"}}


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_lowered_programs_are_the_parents(case):
    assert lowered_programs(*case) == PROGRAMS[case_id(case)]


# ------------------------------------------------ what the spans say

SPANS = ("serving::prefill", "serving::decode_step", "serving::decode.wait",
         "serving::step")
PROMPT = list(range(3, 14))              # 11 tokens: one prefill bucket


def steady(attrs):
    """A span's attributes but its times and the request's id (a count
    over the process)."""
    return {k: v for k, v in attrs.items() if k != "request_id"
            and not k.endswith(("_ms", "_s", "_us"))}


def served_spans(name, variant=None):
    """[(span name, its steady attributes)] of one request of
    `len(PROMPT)` tokens and three more after its first, through a
    Scheduler over the tiny engine: one prefill and three decode calls."""
    eng = tiny_engine(name, variant)
    sched = Scheduler(eng, ServingConfig(max_queue=4))
    log = profiler.span_log()
    before = log.appended
    handle = sched.submit(PROMPT, max_new_tokens=4)
    while sched.step():
        pass
    assert handle.status == "DONE"
    new = log.appended - before
    spans = [dict(zip(SpanLog.FIELDS, r)) for r in log.spans()[-new:]]
    return [(s["name"], steady(s["attrs"])) for s in spans
            if s["name"] in SPANS]


# taken on the same parent as PROGRAMS; a span is (its name, its steady
# attributes)
P, D, W, S = SPANS
SERVED = {
    'ling3_flash_ep4_share': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'bfloat16', 'attend': 'gather',
             'pool_donated': 1, 'ssm_tokens_scanned': 192,
             'ssm_tokens_valid': 66, 'moe_pairs_total': 132,
             'moe_pairs_local': 31, 'moe_experts_hit': 14,
             'moe_expert_max': 6}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 0,
             'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 12,
             'state_slots_in_use': 1, 'state_bytes': 15744,
             'latent_bytes_in_use': 1280, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 12,
             'latent_rows_read': 128, 'call': 0, 'fetches': 1,
             'moe_pairs_total': 12, 'moe_pairs_local': 1,
             'moe_experts_hit': 1, 'moe_expert_max': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 13,
             'state_slots_in_use': 1, 'state_bytes': 15744,
             'latent_bytes_in_use': 1280, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 13,
             'latent_rows_read': 128, 'call': 1, 'fetches': 1,
             'moe_pairs_total': 12, 'moe_pairs_local': 7,
             'moe_experts_hit': 7, 'moe_expert_max': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 14,
             'state_slots_in_use': 1, 'state_bytes': 15744,
             'latent_bytes_in_use': 1280, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 14,
             'latent_rows_read': 128, 'call': 2, 'fetches': 1,
             'moe_pairs_total': 12, 'moe_pairs_local': 4,
             'moe_experts_hit': 4, 'moe_expert_max': 1}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 0,
             'kv_blocks_total': 16, 'kv_tokens_held': 0,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 0, 'prefix_cache_bypassed': 1})],
    'deepseek_v3_ep16_share': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'bfloat16', 'attend': 'gather',
             'pool_donated': 1, 'moe_pairs_total': 88,
             'moe_pairs_local': 31, 'moe_experts_hit': 14,
             'moe_expert_max': 5}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 0,
             'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 12,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 6400, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 60,
             'latent_rows_read': 640, 'call': 0, 'fetches': 1,
             'moe_pairs_total': 8, 'moe_pairs_local': 2,
             'moe_experts_hit': 2, 'moe_expert_max': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 13,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 6400, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 65,
             'latent_rows_read': 640, 'call': 1, 'fetches': 1,
             'moe_pairs_total': 8, 'moe_pairs_local': 3,
             'moe_experts_hit': 3, 'moe_expert_max': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 14,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 6400, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 70,
             'latent_rows_read': 640, 'call': 2, 'fetches': 1,
             'moe_pairs_total': 8, 'moe_pairs_local': 2,
             'moe_experts_hit': 2, 'moe_expert_max': 1}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 0,
             'kv_blocks_total': 16, 'kv_tokens_held': 0,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 0, 'prefix_cache_bypassed': 1})],
    'nemotron3_nano_ep8_share': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'bfloat16', 'attend': 'gather',
             'pool_donated': 1, 'ssm_tokens_scanned': 96,
             'ssm_tokens_valid': 33, 'moe_pairs_total': 99,
             'moe_pairs_local': 14, 'moe_experts_hit': 6,
             'moe_expert_max': 4}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 0,
             'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 12,
             'state_slots_in_use': 1, 'state_bytes': 7872,
             'latent_bytes_in_use': 2048, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 12,
             'latent_rows_read': 128, 'call': 0, 'fetches': 1,
             'moe_pairs_total': 9, 'moe_pairs_local': 0,
             'moe_experts_hit': 0, 'moe_expert_max': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 13,
             'state_slots_in_use': 1, 'state_bytes': 7872,
             'latent_bytes_in_use': 2048, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 13,
             'latent_rows_read': 128, 'call': 1, 'fetches': 1,
             'moe_pairs_total': 9, 'moe_pairs_local': 2,
             'moe_experts_hit': 2, 'moe_expert_max': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 14,
             'state_slots_in_use': 1, 'state_bytes': 7872,
             'latent_bytes_in_use': 2048, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 14,
             'latent_rows_read': 128, 'call': 2, 'fetches': 1,
             'moe_pairs_total': 9, 'moe_pairs_local': 3,
             'moe_experts_hit': 3, 'moe_expert_max': 1}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 0,
             'kv_blocks_total': 16, 'kv_tokens_held': 0,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 0, 'prefix_cache_bypassed': 1})],
    'evabyte_6p5b_pp4_stage': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'bfloat16', 'attend': 'gather',
             'pool_donated': 1, 'eva_windows': 2,
             'eva_chunks_summarised': 6, 'eva_pairs': 54,
             'moe_pairs_total': 0, 'moe_pairs_local': 0,
             'moe_experts_hit': 0, 'moe_expert_max': 0}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 0,
             'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 7,
             'kv_blocks_total': 40, 'kv_tokens_held': 12,
             'window_rows_held': 4, 'summary_rows_held': 4,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 10752, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 24,
             'latent_rows_read': 240, 'call': 0, 'fetches': 1,
             'moe_pairs_total': 0, 'moe_pairs_local': 0,
             'moe_experts_hit': 0, 'moe_expert_max': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 8,
             'kv_blocks_total': 40, 'kv_tokens_held': 13,
             'window_rows_held': 5, 'summary_rows_held': 4,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 12288, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 27,
             'latent_rows_read': 240, 'call': 1, 'fetches': 1,
             'moe_pairs_total': 0, 'moe_pairs_local': 0,
             'moe_experts_hit': 0, 'moe_expert_max': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 8,
             'kv_blocks_total': 40, 'kv_tokens_held': 14,
             'window_rows_held': 6, 'summary_rows_held': 4,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 12288, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'latent_rows_held': 30,
             'latent_rows_read': 240, 'call': 2, 'fetches': 1,
             'moe_pairs_total': 0, 'moe_pairs_local': 0,
             'moe_experts_hit': 0, 'moe_expert_max': 0}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 0,
             'kv_blocks_total': 40, 'kv_tokens_held': 0,
             'window_rows_held': 0, 'summary_rows_held': 0,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 0, 'prefix_cache_bypassed': 1})],
    'keye_vl2_a3b_ep8_share': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'bfloat16', 'attend': 'gather',
             'pool_donated': 1, 'dsa_index_pairs': 132,
             'dsa_selected_pairs': 120, 'moe_pairs_total': 88,
             'moe_pairs_local': 24, 'moe_experts_hit': 7,
             'moe_expert_max': 7, 'dsa_kv_rows_read': 288,
             'dsa_key_blocks_written': 16, 'dsa_select_kernel_blocks': 12,
             'dsa_select_tie_blocks': 3}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 0,
             'call': 0, 'dsa_rows_indexed': 24, 'dsa_rows_selected': 16}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 3,
             'kv_blocks_total': 32, 'kv_tokens_held': 12,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 3456, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'dsa_rows_selected': 16, 'call': 0,
             'fetches': 1, 'moe_pairs_total': 8, 'moe_pairs_local': 4,
             'moe_experts_hit': 4, 'moe_expert_max': 1,
             'dsa_kv_rows_read': 32, 'dsa_key_blocks_written': 4,
             'dsa_select_kernel_blocks': 0, 'dsa_select_tie_blocks': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 1, 'dsa_rows_indexed': 26, 'dsa_rows_selected': 16}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 4,
             'kv_blocks_total': 32, 'kv_tokens_held': 13,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 4608, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'dsa_rows_selected': 16, 'call': 1,
             'fetches': 1, 'moe_pairs_total': 8, 'moe_pairs_local': 2,
             'moe_experts_hit': 2, 'moe_expert_max': 1,
             'dsa_kv_rows_read': 32, 'dsa_key_blocks_written': 4,
             'dsa_select_kernel_blocks': 0, 'dsa_select_tie_blocks': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True,
             'kv_dtype': 'bfloat16', 'attend': 'gather', 'ahead': 1,
             'call': 2, 'dsa_rows_indexed': 28, 'dsa_rows_selected': 16}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 4,
             'kv_blocks_total': 32, 'kv_tokens_held': 14,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 4608, 'prefix_cache_bypassed': 1}),
        (W, {'pool_donated': 1, 'dsa_rows_selected': 16, 'call': 2,
             'fetches': 1, 'moe_pairs_total': 8, 'moe_pairs_local': 6,
             'moe_experts_hit': 6, 'moe_expert_max': 1,
             'dsa_kv_rows_read': 32, 'dsa_key_blocks_written': 4,
             'dsa_select_kernel_blocks': 0, 'dsa_select_tie_blocks': 0}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 0,
             'kv_blocks_total': 32, 'kv_tokens_held': 0,
             'state_slots_in_use': 0, 'state_bytes': 0,
             'latent_bytes_in_use': 0, 'prefix_cache_bypassed': 1})],
    'cerebras_gpt_1p3b': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'float32', 'attend': 'gather',
             'pool_donated': 1}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'float32',
             'attend': 'gather', 'ahead': 0, 'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 12}),
        (W, {'pool_donated': 1, 'call': 0, 'fetches': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'float32',
             'attend': 'gather', 'ahead': 1, 'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 13}),
        (W, {'pool_donated': 1, 'call': 1, 'fetches': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'float32',
             'attend': 'gather', 'ahead': 1, 'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 14}),
        (W, {'pool_donated': 1, 'call': 2, 'fetches': 1}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 1,
             'kv_blocks_total': 16, 'kv_tokens_held': 0})],
    'cerebras_gpt_1p3b-int8': [
        (P, {'bucket': 32, 'length': 11, 'slot': 0, 'prefix_hit_tokens': 0,
             'paged': True, 'kv_dtype': 'int8', 'attend': 'gather',
             'pool_donated': 1}),
        (W, {'fetches': 0}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'int8',
             'attend': 'gather', 'ahead': 0, 'call': 0}),
        (S, {'step': 0, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 12}),
        (W, {'pool_donated': 1, 'call': 0, 'fetches': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'int8',
             'attend': 'gather', 'ahead': 1, 'call': 1}),
        (S, {'step': 1, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 13}),
        (W, {'pool_donated': 1, 'call': 1, 'fetches': 1}),
        (D, {'slots': 2, 'active': 1, 'paged': True, 'kv_dtype': 'int8',
             'attend': 'gather', 'ahead': 1, 'call': 2}),
        (S, {'step': 2, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 1, 'slots': 2, 'kv_blocks_in_use': 2,
             'kv_blocks_total': 16, 'kv_tokens_held': 14}),
        (W, {'pool_donated': 1, 'call': 2, 'fetches': 1}),
        (S, {'step': 3, 'preempted': 0, 'queue_depth': 0,
             'active_slots': 0, 'slots': 2, 'kv_blocks_in_use': 1,
             'kv_blocks_total': 16, 'kv_tokens_held': 0})]}


@pytest.mark.parametrize("case", [c for c in CASES
                                  if c[1] != "decode_kernel"], ids=case_id)
def test_spans_say_what_they_said_on_the_parent(case):
    assert served_spans(*case) == SERVED[case_id(case)]
