"""Compile a configuration's served stage programs for a described TPU
v5e chip, on a host without one: nothing runs, so it shows only what the
TPU compiler refuses and how much device memory each program needs.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse_compile.py vgg19-cifar

The export calibrates on the CPU (random weights, a few images), with the
Pallas kernels lowered for Mosaic rather than interpreted; every segment
is then compiled at the configuration's slot geometry and must hold a
``tpu_custom_call``.  Not a test: it loads the TPU compiler, which one
process at a time may hold.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(name):
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(os.path.dirname(HERE), 'src'))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import loadgen
    from repro.core.export import export_cnn
    from repro.kernels import ops

    jax.config.update('jax_enable_compilation_cache', False)
    cfg = harness.load_json(os.path.join(HERE, 'configs', name + '.json'))
    ref = harness.load_module(os.path.join(HERE, cfg['reference']), 'ref')
    key = harness.key_from_seed(7)
    params = ref.init(jax.random.fold_in(key, 0), cfg)
    calib = loadgen.make_images(
        jax.random.fold_in(key, 1), 16, size=cfg['image_size'],
        channels=cfg['in_channels'], classes=cfg['num_classes'], jitter=3,
        difficulty=0.8, scale_sd=0.1)
    model = export_cnn(params, harness.program_config(cfg, cfg['w_bits']),
                       use_pallas=True, calibrate=calib)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    ops._interpret = lambda: False          # lower Mosaic kernels
    carry = jax.ShapeDtypeStruct(
        (cfg['slots'], cfg['image_size'], cfg['image_size'],
         cfg['in_channels']), jax.numpy.float32)
    p = jax.tree.map(spec, model.params)
    for k, fn in enumerate(model.stage_fns):
        c = jax.tree.map(spec, carry)
        compiled = fn.lower(p, c).compile()
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        print(json.dumps({
            'segment': k, 'tpu_custom_calls': text.count('tpu_custom_call'),
            'temp_bytes': getattr(mem, 'temp_size_in_bytes', None),
            'argument_bytes': getattr(mem, 'argument_size_in_bytes', None)}),
            flush=True)
        if 'tpu_custom_call' not in text:
            raise SystemExit(f'segment {k} holds no Mosaic kernel')
        if k < len(model.stage_fns) - 1:
            carry = jax.eval_shape(fn, model.params, carry)[1]


if __name__ == '__main__':
    main(sys.argv[1])
