"""Dry-run launch-path integration: lower+compile a reduced combo on a
small mesh (the real 512-device sweep is results/dryrun_*.jsonl; this
keeps the path covered in CI). Runs in-process on the forced multi-device
host CPU that tests/conftest.py sets up before jax initializes."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from repro.configs.base import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.models.model import Model, abstract_init
from repro.roofline.collect import collective_bytes
from repro.sharding import rules


@pytest.mark.requires_devices(8)
@pytest.mark.parametrize("arch", ["phi4_mini_3p8b", "qwen2_moe_a2p7b",
                                  "mamba2_780m"])
def test_reduced_dryrun_on_2x4_mesh(arch):
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = reduced(get_config(arch))
    model = Model(cfg)
    params_shapes, logical = abstract_init(model)
    # exercises rules.spec for every parameter (raises on a bad rule)
    jax.tree.map(lambda lg: NamedSharding(mesh, rules.spec(lg, mesh)),
                 logical, is_leaf=lambda x: isinstance(x, tuple))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    if cfg.arch_type == "vlm":
        batch["vision_embeds"] = jax.ShapeDtypeStruct(
            (4, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.arch_type == "audio":
        batch["frames"] = jax.ShapeDtypeStruct(
            (4, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)

    def fwd(p, b):
        return model.forward(p, b)[0]

    with jax.set_mesh(mesh):
        lowered = jax.jit(fwd).lower(params_shapes, batch)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    coll = collective_bytes(compiled.as_text())
    assert coll["total_bytes"] >= 0
