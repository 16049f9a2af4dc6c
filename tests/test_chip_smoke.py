"""The guards around a chip run: ``chip_smoke.py`` refuses anything but a
TPU, the compile cache sits at one fixed place, and a peak is never made
up for a device the table does not know."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env):
    full_env = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
    full_env.update(env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full_env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr, proc.stderr[-2000:]
    # no result line: nothing on stdout claims ok
    assert '"ok"' not in proc.stdout


_PROBE = ("import jax; "
          "from paddle_tpu.framework.compile_cache import "
          "configure_compile_cache as c; "
          "print(c()); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_is_fixed_inside_the_checkout(tmp_path):
    """Unset: the same in-checkout, git-ignored directory from two working
    directories (two processes)."""
    outs = []
    for cwd in (REPO, str(tmp_path)):
        proc = _run(["-c", _PROBE], cwd=cwd, PYTHONPATH=REPO,
                    JAX_PLATFORMS="cpu")
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), (
            ".jax_cache/ is not git-ignored")


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it, code sets nothing."""
    placed = str(tmp_path / "cache")
    proc = _run(["-c", _PROBE], PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=placed)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [placed, placed]


@pytest.mark.parametrize("placed", [None, "somewhere"])
def test_compile_cache_keys_on_metadata(tmp_path, placed):
    """The step programs' scope names are HLO metadata and a device trace
    reads them out of the executable; JAX's default key strips metadata, so
    the parent of a renamed program and the program itself would share one
    entry (the tiny unified step's ``jit_step`` does, on the CPU)."""
    env = dict(PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / placed)
    proc = _run(["-c", "import jax; from paddle_tpu.framework.compile_cache "
                 "import configure_compile_cache as c; c(); print(jax.config."
                 "jax_compilation_cache_include_metadata_in_key)"], **env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["True"]


def test_chip_peak_raises_on_an_unknown_device():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench._chip_peak("TPU v5 lite") == ("TPU v5 lite", 197e12)
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench._chip_peak("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench._chip_peak("cpu")


# the forms a pool-sized move takes in the compiled serving step (TPU HLO of
# the 590M deployment, cut down): a fused in-place update inside the
# copy-on-write loop, whose name sits two callers up, and the parent's three
# kinds of whole-pool copy
_HLO = """
%fused_computation.25 (param_0.1: bf16[18,768,12,64,128], param_1.2: s32[]) -> bf16[18,768,12,64,128] {
  ROOT %dynamic-update-slice.20 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.1, %select.4, %param_1.2)
}
%wide.while_body.2 (wide.param.1: (s32[], bf16[18,768,12,64,128])) -> (s32[], bf16[18,768,12,64,128]) {
  %fusion.219 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.850), kind=kLoop, calls=%fused_computation.25
}
%fused_computation.9 (param_0.7: bf16[18,768,12,64,128], param_1.8: s32[]) -> bf16[18,768,12,64,128] {
  ROOT %dynamic_update_slice.13 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.7, %copy.78, %param_1.8)
}
%region_3.22 (arg_tuple.0: (s32[], bf16[512,1536])) -> (s32[], bf16[512,1536]) {
  %copy.83 = bf16[768,12,64,128]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.200), metadata={op_name="jit(step)/layers/while/body/closed_call/kv_write/scatter" stack_frame_id=69}
  %stacked.4 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.9), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/layers/while/body/dynamic_update_slice"}
  %paged_kv_write.8 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(%a, %b), custom_call_target="tpu_custom_call"
}
ENTRY %main.48 (args_11_.1: bf16[18,768,12,64,128]) -> bf16[18,768,12,64,128] {
  %while.11 = (s32[], bf16[18,768,12,64,128]) while(%tuple.183), condition=%wide.while_cond.2, body=%wide.while_body.2, metadata={op_name="jit(step)/cow/gather" stack_frame_id=25}
  %copy.137 = bf16[18,768,12,64,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.1007), backend_config={"flag_configs":[]}
  %copy.5 = bf16[512,12,128]{2,1,0:T(8,128)(2,1)} copy(%fusion.3)
}
"""


def test_pool_copies_finds_whole_pool_moves_and_lets_cow_through():
    sys.path.insert(0, REPO)
    try:
        from chip_smoke import pool_copies
    finally:
        sys.path.remove(REPO)
    found = pool_copies(_HLO, (18, 768, 12, 64, 128))
    assert [line.split(" = ")[0].split("%")[-1] for line in found] == [
        "dynamic_update_slice.13", "copy.83", "copy.137"]
    # another deployment's pool: nothing here has its shape
    assert pool_copies(_HLO, (24, 136, 12, 64, 128)) == []
