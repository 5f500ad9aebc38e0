"""Golden numbers of the harness's Qwen2 architecture code, first taken on
the tree from before it moved into ``chipbench/arch/``: the tiny
configuration's weights at seed 5 leaf by leaf, the work counts of both
served configurations, the program configuration, and the reference's
readings with and without each control.  None of them may move."""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from chipbench import model, reference, work
from chipbench.tests import tiny

SEED = 5
PROMPT = [3, 14, 15, 92, 65, 35, 89, 79, 32, 38, 46, 26]
SERVED = [433, 83, 279, 50, 288, 419, 71, 69]
CTX = {"short": [10, 20], "mixed": [1, 129, 640, 333, 257, 96, 512, 2]}
LENS = {"short": [3, 5], "mixed": [128, 256, 3072, 512, 1]}


def _arch(doc):
    """What maps ``doc`` (``dims``, ``program_config``): its
    architecture's module, or ``chipbench.model`` on a tree from before the
    lookup, where these numbers were first taken."""
    try:
        from chipbench.arch import arch_for
    except ImportError:
        return model
    return arch_for(doc)


def _dims(name):
    doc = tiny.CONFIG if name == "tiny" else model.load_config(name)
    return _arch(doc).dims(doc)


def param_digests() -> dict:
    """path -> (shape, dtype, sha256 of the bytes, first 16 hex digits)."""
    params = model.make_params(_dims("tiny"), SEED, "bfloat16")
    flat, _ = jax.tree.flatten_with_path(params)
    out = {}
    for path, leaf in flat:
        a = np.asarray(leaf)
        digest = hashlib.sha256(a.tobytes()).hexdigest()[:16]
        out["/".join(str(getattr(k, "key", k)) for k in path)] = (
            a.shape, str(a.dtype), digest)
    return out


def work_numbers(name: str) -> dict:
    m = _dims(name)
    out = {"params": m.params, "kv_bytes_per_token": m.kv_bytes_per_token}
    for k, ctx in CTX.items():
        w = work.decode_step(m, ctx)
        out[f"decode.{k}"] = (w.flops, w.byts)
    for k, lens in LENS.items():
        w = work.prefill(m, lens)
        out[f"prefill.{k}"] = (w.flops, w.byts)
    return out


def score_numbers(control) -> dict:
    m = _dims("tiny")
    params = model.make_params(m, SEED, "bfloat16")
    r = reference.score(params, m, PROMPT, SERVED, length=64,
                        control=control)
    return {k: [float(x) for x in v] for k, v in r.items()}


GOLDEN_PARAMS = {'blocks/attn/bk': ((2, 32), 'bfloat16', '52beb0d15d6efee2'),
 'blocks/attn/bq': ((2, 64), 'bfloat16', '2674079699758947'),
 'blocks/attn/bv': ((2, 32), 'bfloat16', '7b10624c742623b2'),
 'blocks/attn/wk': ((2, 64, 32), 'bfloat16', '55f34917e76457eb'),
 'blocks/attn/wo': ((2, 64, 64), 'bfloat16', 'baa05aeecdbfaeb9'),
 'blocks/attn/wq': ((2, 64, 64), 'bfloat16', '3dd9de0772889582'),
 'blocks/attn/wv': ((2, 64, 32), 'bfloat16', '0129e19751c3b509'),
 'blocks/ln1': ((2, 64), 'bfloat16', '2c1df275bbf6b4fa'),
 'blocks/ln2': ((2, 64), 'bfloat16', 'e7131c6f7fa8e67a'),
 'blocks/mlp/w1': ((2, 64, 128), 'bfloat16', '1f290efcdd5b68d0'),
 'blocks/mlp/w2': ((2, 128, 64), 'bfloat16', '48624d3908c2225b'),
 'blocks/mlp/w3': ((2, 64, 128), 'bfloat16', '30f0ac0b1e1725ae'),
 'embed': ((512, 64), 'bfloat16', 'eef3d951a85befde'),
 'lm_head': ((64, 512), 'bfloat16', '2822e2f6b9033c9b'),
 'ln_f': ((64,), 'bfloat16', 'ee840d02d0eab944')}
GOLDEN_WORK = {'qwen1.5-4b': {'decode.mixed': (57781288960.0, 7934641152.0),
                'decode.short': (14255882240.0, 7136339968.0),
                'kv_bytes_per_token': 409600,
                'params': 3950369280,
                'prefill.mixed': (27186780979200.0, 8771888640.0),
                'prefill.short': (52315504640.0, 7127359488.0)},
 'qwen2-7b.pp4': {'decode.mixed': (35018805248.0, 4385976320.0),
                  'decode.short': (8708288512.0, 4354471936.0),
                  'kv_bytes_per_token': 14336,
                  'params': 2721402880,
                  'prefill.mixed': (13445867997184.0, 4441201664.0),
                  'prefill.short': (28283250688.0, 4354199552.0)}}
GOLDEN_SCORE = {'None': {'best': [3.5905799865722656,
                   2.840944290161133,
                   2.606649875640869,
                   2.9343466758728027,
                   3.6748850345611572,
                   3.3033406734466553,
                   2.5756359100341797,
                   2.7900726795196533],
          'served': [0.34738144278526306,
                     -1.6392099857330322,
                     1.4671366214752197,
                     0.5773046016693115,
                     0.4761182963848114,
                     0.004061171784996986,
                     -1.1374003887176514,
                     0.4069335460662842]},
 'fp8': {'best': [3.5905799865722656,
                  2.840944290161133,
                  2.606649875640869,
                  2.9343466758728027,
                  3.6748850345611572,
                  3.3033406734466553,
                  2.5756359100341797,
                  2.7900726795196533],
         'control': [3.5905799865722656,
                     2.840944290161133,
                     2.606649875640869,
                     2.9343466758728027,
                     3.6748850345611572,
                     3.3033406734466553,
                     2.517167806625366,
                     2.6707406044006348],
         'control_own': [3.593837022781372,
                         2.8118484020233154,
                         2.715080499649048,
                         2.9257640838623047,
                         3.6094114780426025,
                         3.3031256198883057,
                         2.544966459274292,
                         2.724623680114746],
         'control_tok': [175.0,
                         169.0,
                         511.0,
                         27.0,
                         169.0,
                         216.0,
                         289.0,
                         175.0],
         'served': [0.34738144278526306,
                    -1.6392099857330322,
                    1.4671366214752197,
                    0.5773046016693115,
                    0.4761182963848114,
                    0.004061171784996986,
                    -1.1374003887176514,
                    0.4069335460662842]},
 'int8': {'best': [3.5905799865722656,
                   2.840944290161133,
                   2.606649875640869,
                   2.9343466758728027,
                   3.6748850345611572,
                   3.3033406734466553,
                   2.5756359100341797,
                   2.7900726795196533],
          'control': [3.5905799865722656,
                      2.840944290161133,
                      2.555415630340576,
                      2.9343466758728027,
                      3.6748850345611572,
                      3.3033406734466553,
                      2.5756359100341797,
                      2.7900726795196533],
          'control_own': [3.6102633476257324,
                          2.858293294906616,
                          2.5985138416290283,
                          2.970909595489502,
                          3.680779218673706,
                          3.2747933864593506,
                          2.61386775970459,
                          2.761741876602173],
          'control_tok': [175.0,
                          169.0,
                          496.0,
                          27.0,
                          169.0,
                          216.0,
                          473.0,
                          264.0],
          'served': [0.34738144278526306,
                     -1.6392099857330322,
                     1.4671366214752197,
                     0.5773046016693115,
                     0.4761182963848114,
                     0.004061171784996986,
                     -1.1374003887176514,
                     0.4069335460662842]}}
GOLDEN_PROGRAM = {'act': 'silu',
 'attn_kv_chunk': 32,
 'attn_q_chunk': 16,
 'attn_window': 0,
 'capacity_factor': 1.25,
 'd_ff': 128,
 'd_head': 16,
 'd_model': 64,
 'dtype': 'bfloat16',
 'enc_layers': 0,
 'enc_seq': 0,
 'family': 'dense',
 'global_layers': (),
 'max_seq': 8192,
 'n_experts': 0,
 'n_heads': 4,
 'n_img_tokens': 0,
 'n_kv_heads': 2,
 'n_layers': 2,
 'n_meta_tokens': 0,
 'n_shared_experts': 0,
 'name': 'tiny',
 'norm_eps': 1e-06,
 'param_count_hint': 0.0,
 'qkv_bias': True,
 'remat': 'full',
 'rope_theta': 10000.0,
 'router_aux_coef': 0.01,
 'source': '',
 'ssm_chunk': 256,
 'ssm_conv': 4,
 'ssm_expand': 2,
 'ssm_groups': 1,
 'ssm_head_dim': 64,
 'ssm_heads': 0,
 'ssm_state': 0,
 'tie_embeddings': False,
 'top_k': 0,
 'vocab': 512}


def test_weights_are_drawn_as_before():
    assert param_digests() == GOLDEN_PARAMS


@pytest.mark.parametrize("name", ["qwen1.5-4b", "qwen2-7b.pp4"])
def test_work_counts_are_as_before(name):
    assert work_numbers(name) == GOLDEN_WORK[name]


@pytest.mark.parametrize("control", [None, "int8", "fp8"])
def test_reference_reads_as_before(control):
    # XLA's CPU matmuls sum in an order that follows the process's thread
    # count, which moves a reading by a few float32 ulps; a change to the
    # forward moves it by orders more
    got, want = score_numbers(control), GOLDEN_SCORE[str(control)]
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-5,
                                   err_msg=k)


def test_program_config_is_as_before():
    cfg = _arch(tiny.CONFIG).program_config(dict(tiny.CONFIG))
    assert dataclasses.asdict(cfg) == GOLDEN_PROGRAM
