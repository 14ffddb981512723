"""Pipeline parallelism: schedule math + multi-stage parity (subprocess)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import PipelineConfig


def test_schedule_accounting():
    cfg = PipelineConfig(n_stages=4, n_microbatches=12)
    assert cfg.n_ticks == 15
    assert cfg.bubble_fraction == pytest.approx(3 / 15)


def test_bubble_shrinks_with_microbatches():
    b = [PipelineConfig(4, m).bubble_fraction for m in (4, 16, 64)]
    assert b[0] > b[1] > b[2]


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import (PipelineConfig, make_pipelined_mlp,
                                            pipeline_apply, reference_apply)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    cfg = PipelineConfig(n_stages=4, n_microbatches=8, axis_name="stage")
    stacked, stage_fn = make_pipelined_mlp(cfg, [16]*9, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))   # (M, mb, d)

    def run(params, x):
        # shard_map keeps a leading size-1 stage dim on the local shard
        return pipeline_apply(stage_fn, cfg, params[0], x)

    outs = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("stage"), P()), out_specs=P("stage"), check_vma=False,
    ))(stacked, x)
    # out_specs P('stage') stacks per-stage outputs on axis 0: the LAST
    # stage's block holds the real outputs
    got = outs.reshape(4, 8 // 1, *outs.shape[1:])[-1] if False else outs
    # outs: (4*8, 4, 16) -> last stage block
    got = outs.reshape(4, 8, 4, 16)[-1]
    want = reference_apply(stacked, x.reshape(8, 4, 16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("PIPELINE PARITY OK")
""")


@pytest.mark.slow
def test_pipeline_parity_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "PIPELINE PARITY OK" in r.stdout, (
        f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-2000:]}")
