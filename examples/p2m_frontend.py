"""P2M binary-spike front-end as a language model's vision input.

The paper's technique is a *sensor front-end*; for a vision-language model
it takes the vision tower's place: the in-pixel layer emits binary spike
maps, a projector (``models/projector.py``) turns their 14x14 patches into
image embeddings at the first positions of the prompt, and the decoder
answers — an ADC-less, 1-bit-link camera feeding an LLM. This runs the
normal serving path (``ServingEngine`` with ``frontend=``) on a reduced
Kimi-VL-A3B decoder.

    PYTHONPATH=src python examples/p2m_frontend.py
"""
import jax
import jax.numpy as jnp

from repro import configs, frontend
from repro.configs.reduced import reduced
from repro.core import p2m
from repro.models import lm, projector
from repro.models.params import init_tree
from repro.serving import ServingEngine

PATCH = projector.PATCH


def main() -> None:
    cfg = reduced(configs.get_arch("kimi-vl-a3b"))
    print("decoder:", cfg.name, "(reduced)")
    fcfg = frontend.FrontendConfig(p2m=p2m.P2MConfig(out_channels=32),
                                   backend="device")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    params["p2m"] = p2m.init_params(jax.random.PRNGKey(1), fcfg.p2m)
    params["projector"] = init_tree(
        jax.random.PRNGKey(2), projector.spec(PATCH * PATCH * 32, 128,
                                              cfg.d_model), cfg.pdtype)

    # the camera: two 56x56 frames -> 28x28x32 spike maps -> 4 image tokens
    frames = jax.random.uniform(jax.random.PRNGKey(3), (2, 56, 56, 3))
    images = (56 // fcfg.p2m.stride // PATCH) ** 2
    text = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                              cfg.vocab_size)
    prompts = jnp.concatenate([jnp.zeros((2, images), jnp.int32), text], 1)

    engine = ServingEngine(cfg, params, max_len=prompts.shape[1] + 8,
                           frontend=fcfg, seed=5)
    out = engine.serve(prompts, 8, frames=frames)
    fe = out["frontend"][0]
    print(f"spike rate {float(jnp.mean(fe['channel_rates'])) * 100:.1f}%, "
          f"{images} image tokens + {text.shape[1]} text tokens per request")
    print("generated:", out["tokens"].tolist())

    # the link the paper optimizes: sensor -> backbone traffic
    raw_bits = frames.size * 12
    spike_bits = frames.shape[0] * (56 // 2) ** 2 * 32
    print(f"sensor link: {raw_bits} bits raw vs {spike_bits} bits binary "
          f"spikes ({raw_bits / spike_bits:.1f}x reduction before sparse "
          f"coding)")


if __name__ == "__main__":
    main()
