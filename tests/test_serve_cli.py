"""`cli.py serve`: a checkpoint server and nothing else (serve/cli.py).

The one user-facing serving command loads a checkpoint, puts the prompts
through PagedEngine + Scheduler and prints what `generate.py` prints for
the same checkpoint under greedy; every flag of the retired bench is
argparse's exit 2.
"""

import jax
import jax.numpy as jnp
import pytest

from ddp_practice_tpu import checkpoint as ckpt
from ddp_practice_tpu import cli
from ddp_practice_tpu import generate as generate_cli
from ddp_practice_tpu.config import TrainConfig
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve.cli import build_parser
from ddp_practice_tpu.train.state import create_state, make_optimizer

SEQ, VOCAB = 48, 64


@pytest.fixture(scope="module")
def lm_ckpt(tmp_path_factory):
    """An lm_tiny RoPE checkpoint as the Trainer writes one (the manifest
    fields load_lm rebuilds the model from), without the training."""
    model = create_model("lm_tiny", vocab_size=VOCAB, max_len=SEQ,
                         pos_emb="rope")
    state = create_state(
        model, make_optimizer(TrainConfig(model="lm_tiny")),
        rng=jax.random.PRNGKey(0),
        sample_input=jnp.zeros((1, SEQ), jnp.int32),
    )
    path = str(tmp_path_factory.mktemp("ck"))
    ckpt.save(path, state, extra={
        "step": 0, "model": "lm_tiny", "precision_policy": "fp32",
        "seq_len": SEQ, "vocab_size": VOCAB, "pos_emb": "rope",
    })
    return path


def test_serve_prints_what_generate_prints(lm_ckpt, capsys, devices):
    common = ["--ckpt_dir", lm_ckpt, "--prompt", "\n",
              "--max_new_tokens", "12"]
    assert generate_cli.main(common + ["--temperature", "0"]) == 0
    text = capsys.readouterr().out[:-1]  # print's own newline
    assert cli.main(["serve"] + common) == 0
    out = capsys.readouterr().out
    assert out.startswith("[serve] platform=cpu ")
    assert "--- request 0 [length] ttft " in out
    assert "\n" + text + "\n" in out  # the prompt, then generate's tokens


@pytest.mark.parametrize("argv", [
    [], ["--ckpt_dir", "x", "--procs", "2"],
    ["--ckpt_dir", "x", "--requests", "8"],
], ids=["no-checkpoint", "procs", "requests"])
def test_serve_refuses_what_it_does_not_serve(argv, capsys):
    with pytest.raises(SystemExit) as exit_:
        cli.main(["serve"] + argv)
    assert exit_.value.code == 2


def test_serve_parser_has_its_eleven_flags_and_no_other():
    flags = {a.dest for a in build_parser()._actions} - {"help"}
    assert flags == {
        "ckpt_dir", "prompt", "max_new_tokens", "temperature", "top_k",
        "top_p", "eos_id", "max_slots", "decode_burst", "seed",
        "trace_out",
    }
    args = build_parser().parse_args(
        ["--ckpt_dir", "x", "--trace_out", "t.json"])
    assert args.trace_out == "t.json"  # the underscore alias
