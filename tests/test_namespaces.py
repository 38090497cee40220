"""M16 namespace tests: static graph facade, utils, sparse, quantization,
vision, audio."""

import numpy as np
import pytest

import paddle_tpu as pt


class TestStatic:
    def test_program_guard_data_executor(self):
        from paddle_tpu import static
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [None, 4])
            y = static.data("y", [None, 4])
            z = (x * 2 + y).sum(axis=1)
            loss = z.mean()
        exe = static.Executor()
        xv = np.ones((3, 4), "float32")
        yv = np.full((3, 4), 2.0, "float32")
        z_out, l_out = exe.run(main, feed={"x": xv, "y": yv},
                               fetch_list=[z, loss])
        np.testing.assert_allclose(z_out, np.full(3, 16.0), rtol=1e-6)
        assert abs(float(l_out) - 16.0) < 1e-5

    def test_executor_caches_compilation(self):
        from paddle_tpu import static
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [None, 2])
            y = x.exp().sum()
        exe = static.Executor()
        exe.run(main, feed={"x": np.zeros((2, 2), "float32")}, fetch_list=[y])
        n_cached = len(main._cache)
        exe.run(main, feed={"x": np.ones((2, 2), "float32")}, fetch_list=[y])
        assert len(main._cache) == n_cached  # same signature → cache hit
        exe.run(main, feed={"x": np.ones((5, 2), "float32")}, fetch_list=[y])
        assert len(main._cache) == n_cached + 1

    def test_static_nn_fc_and_apply(self):
        from paddle_tpu import static
        pt.seed(0)
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [None, 8])
            h = static.nn.fc(x, 16, activation="relu")
            out = static.apply(lambda v: v.mean(), h)
        r = static.Executor().run(
            main, feed={"x": np.random.randn(4, 8).astype("float32")},
            fetch_list=out)
        assert np.isfinite(r).all()

    def test_default_main_program(self):
        from paddle_tpu import static
        x = static.data("q", [2, 2])
        assert x.name in static.default_main_program().vars


class TestUtils:
    def test_run_check_and_unique_name(self, capsys):
        assert pt.utils.run_check()
        assert "successfully" in capsys.readouterr().out
        a = pt.utils.unique_name.generate("fc")
        b = pt.utils.unique_name.generate("fc")
        assert a == "fc_0" and b == "fc_1"
        with pt.utils.unique_name.guard():
            assert pt.utils.unique_name.generate("fc") == "fc_0"
        assert pt.utils.unique_name.generate("fc") == "fc_2"

    def test_deprecated_and_try_import(self):
        @pt.utils.deprecated(update_to="new_fn", since="0.1")
        def old_fn():
            return 42
        with pytest.warns(DeprecationWarning):
            assert old_fn() == 42
        assert pt.utils.try_import("math") is not None
        with pytest.raises(ImportError):
            pt.utils.try_import("definitely_not_installed_xyz")


class TestSparse:
    def test_coo_roundtrip_and_ops(self):
        import paddle_tpu.sparse as sp
        indices = np.array([[0, 1, 2], [1, 2, 0]])
        values = np.array([1.0, 2.0, 3.0], "float32")
        s = sp.sparse_coo_tensor(indices, values, (3, 3))
        assert s.nnz() == 3
        dense = np.asarray(s.to_dense())
        want = np.zeros((3, 3), "float32")
        want[0, 1], want[1, 2], want[2, 0] = 1, 2, 3
        np.testing.assert_array_equal(dense, want)
        # add
        s2 = sp.add(s, s)
        np.testing.assert_array_equal(np.asarray(s2.to_dense()), want * 2)
        # relu keeps structure
        neg = sp.sparse_coo_tensor(indices, -values, (3, 3))
        np.testing.assert_array_equal(np.asarray(sp.relu(neg).to_dense()),
                                      np.zeros((3, 3)))
        # spmm
        d = np.random.randn(3, 4).astype("float32")
        np.testing.assert_allclose(np.asarray(sp.matmul(s, d)), want @ d,
                                   rtol=1e-5)

    def test_csr_to_dense_and_coo(self):
        import paddle_tpu.sparse as sp
        # matrix [[1,0,2],[0,0,3]]
        s = sp.sparse_csr_tensor([0, 2, 3], [0, 2, 2], [1.0, 2.0, 3.0],
                                 (2, 3))
        want = np.array([[1, 0, 2], [0, 0, 3]], "float32")
        np.testing.assert_array_equal(np.asarray(s.to_dense()), want)
        coo = s.to_sparse_coo()
        np.testing.assert_array_equal(np.asarray(coo.to_dense()), want)

    def test_masked_matmul(self):
        import paddle_tpu.sparse as sp
        x = np.random.randn(3, 4).astype("float32")
        y = np.random.randn(4, 3).astype("float32")
        mask = sp.sparse_coo_tensor([[0, 2], [1, 0]], [1.0, 1.0], (3, 3))
        out = sp.masked_matmul(x, y, mask)
        full = x @ y
        dense = np.asarray(out.to_dense())
        np.testing.assert_allclose(dense[0, 1], full[0, 1], rtol=1e-5)
        np.testing.assert_allclose(dense[2, 0], full[2, 0], rtol=1e-5)
        assert dense[1, 1] == 0


class TestQuantization:
    def test_fake_quant_close_and_ste_grad(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.quantization import FakeQuanterWithAbsMax
        # seeded: the unseeded global stream made this order-dependent —
        # ~1% of draws put a SECOND element on a rounding/clip tie where
        # the STE subgradient is 0.5 (only the argmax was excluded below)
        x = np.random.RandomState(0).randn(32).astype("float32")
        fq = FakeQuanterWithAbsMax(bits=8)
        out = np.asarray(fq(jnp.asarray(x)))
        assert np.abs(out - x).max() < np.abs(x).max() / 100  # 8-bit error
        g = np.asarray(jax.grad(lambda v: (fq(v) ** 2).sum())(jnp.asarray(x)))
        # STE: grad flows everywhere; the abs-max element sits exactly on
        # the clip boundary where jax's min/max gradient is 0.5 at ties —
        # exclude it from the exact comparison
        keep = np.arange(len(x)) != np.abs(x).argmax()
        np.testing.assert_allclose(g[keep], (2 * out)[keep], rtol=1e-4,
                                   atol=1e-5)
        assert np.isfinite(g).all()

    def test_qat_quantize_and_train(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu import nn
        from paddle_tpu.quantization import QAT, QuantConfig
        from paddle_tpu.nn.layer import functional_call, raw_params
        from paddle_tpu.optimizer import AdamW

        pt.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        qat = QAT(QuantConfig(weight_bits=8))
        model = qat.quantize(model)
        x = jnp.asarray(np.random.randn(16, 8).astype("float32"))
        y = jnp.asarray(np.random.randn(16, 2).astype("float32"))
        opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
        params = raw_params(model)
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            def loss(p):
                return ((functional_call(model, p, x) - y) ** 2).mean()
            l, g = jax.value_and_grad(loss)(params)
            params, state = opt.apply(g, state, params)
            return params, state, l

        l0 = None
        for _ in range(25):
            params, state, l = step(params, state)
            if l0 is None:
                l0 = float(l)
        assert float(l) < l0 * 0.7

        # write trained params back, then convert → int8 weights materialized
        for k, v in params.items():
            model._assign_by_path(k, v)
        qat.convert(model)
        lin = model[0]
        assert hasattr(lin, "weight_quant") and lin.weight_quant.dtype == jnp.int8


class TestQuantFixes:
    def test_qat_wraps_attribute_access_models(self):
        """The wrapper must be visible through self.fc, not just
        _sub_layers — models call sublayers by attribute."""
        import jax.numpy as jnp
        from paddle_tpu import nn
        from paddle_tpu.quantization import QAT, _QuantWrapper

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 4)

            def forward(self, x):
                return self.fc(x)

        pt.seed(0)
        m = QAT().quantize(M())
        assert isinstance(m.fc, _QuantWrapper)
        x = jnp.asarray(np.random.randn(2, 4).astype("float32"))
        out_model = np.asarray(m(x))
        out_wrapper = np.asarray(m._sub_layers["fc"](x))
        np.testing.assert_allclose(out_model, out_wrapper, rtol=1e-6)

    def test_quantize_absmax_wide_bits(self):
        from paddle_tpu.quantization import quantize_absmax, dequantize
        import jax.numpy as jnp
        x = np.random.randn(64).astype("float32") * 10
        q, s = quantize_absmax(jnp.asarray(x), bits=16)
        assert q.dtype == jnp.int16
        np.testing.assert_allclose(np.asarray(dequantize(q, s)), x,
                                   atol=np.abs(x).max() / 30000)

    def test_ptq_observes_then_converts(self):
        import jax.numpy as jnp
        from paddle_tpu import nn
        from paddle_tpu.quantization import PTQ

        pt.seed(0)
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        ptq = PTQ()
        m = ptq.quantize(m)
        x = jnp.asarray(np.random.randn(16, 4).astype("float32") * 3)
        before = np.asarray(m(x))  # observation pass is TRANSPARENT
        ref = np.asarray(m(x))
        np.testing.assert_allclose(before, ref, rtol=1e-6)
        ptq.convert(m)
        lin = m[0]
        assert hasattr(lin, "act_scale") and float(lin.act_scale) > 0
        assert hasattr(lin, "weight_quant")
        after = np.asarray(m(x))
        np.testing.assert_allclose(after, before, atol=0.1)  # 8-bit weights


class TestVision:
    def test_transforms_pipeline(self):
        from paddle_tpu.vision import transforms as T
        img = (np.random.rand(40, 60, 3) * 255).astype("uint8")
        pipe = T.Compose([T.Resize(32), T.CenterCrop(32), T.ToTensor(),
                          T.Normalize([0.5] * 3, [0.5] * 3)])
        out = pipe(img)
        assert out.shape == (3, 32, 32)
        assert out.dtype == np.float32 and np.abs(out).max() <= 1.0 + 1e-6

    def test_resize_shorter_edge(self):
        from paddle_tpu.vision.transforms import Resize
        img = np.zeros((40, 80, 3), "float32")
        out = Resize(20)(img)
        assert out.shape == (20, 40, 3)

    def test_lenet_and_resnet18_train_step(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.vision.models import LeNet, resnet18
        from paddle_tpu.nn.layer import functional_call, raw_params

        pt.seed(0)
        m = LeNet()
        x = jnp.zeros((2, 1, 28, 28))
        assert m(x).shape == (2, 10)

        r = resnet18(num_classes=10)
        x = jnp.zeros((1, 3, 32, 32))
        p = raw_params(r)
        out = jax.jit(lambda p: functional_call(r, p, x))(p)
        assert out.shape == (1, 10)
        g = jax.jit(jax.grad(
            lambda p: functional_call(r, p, x, training=True).sum()))(p)
        assert all(np.isfinite(np.asarray(v)).all() for v in g.values())

    def test_random_dataset_with_loader(self):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.vision.datasets import RandomDataset
        from paddle_tpu.vision import transforms as T
        ds = RandomDataset(num_samples=8, image_shape=(3, 8, 8))
        dl = DataLoader(ds, batch_size=4)
        batches = list(dl)
        assert batches[0][0].shape == (4, 3, 8, 8)
        assert batches[0][1].dtype == np.int64


class TestAudio:
    def test_stft_parseval_and_mel(self):
        import paddle_tpu.audio as audio
        t = np.linspace(0, 1, 4000, dtype="float32")
        x = np.sin(2 * np.pi * 440 * t)
        spec = np.asarray(audio.spectrogram(x, n_fft=256, hop_length=128))
        assert spec.shape[0] == 129
        # peak bin should be near 440Hz: bin = 440/ (4000/2) * 128
        peak = spec.mean(-1).argmax()
        want_bin = round(440 / (4000 / 2) * 128)
        assert abs(int(peak) - want_bin) <= 1
        mel = audio.MelSpectrogram(sr=4000, n_fft=256, n_mels=20)(x)
        assert mel.shape[0] == 20
        assert np.isfinite(np.asarray(mel)).all()


class TestVisionZoo:
    """New model families (reference python/paddle/vision/models/):
    forward shape + finite grads on tiny inputs."""

    def _check(self, model, in_shape=(1, 3, 64, 64), n_cls=10):
        # forward and backward as one compiled program each: traced op by
        # op a model compiles hundreds of small programs (densenet121 cold,
        # one process: 173 s, against 21 s under jit; PR 27)
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.layer import functional_call, raw_params
        x = jnp.ones(in_shape, jnp.float32)
        p = raw_params(model)
        out = jax.jit(lambda p: functional_call(model, p, x))(p)
        assert out.shape == (in_shape[0], n_cls)
        g = jax.jit(jax.grad(
            lambda p: functional_call(model, p, x, training=True).sum()))(p)
        leaves = jax.tree_util.tree_leaves(g)
        assert leaves and all(np.isfinite(np.asarray(v)).all()
                              for v in leaves)

    def test_vgg11_bn(self):
        from paddle_tpu.vision.models import vgg11
        pt.seed(0)
        self._check(vgg11(batch_norm=True, num_classes=10))

    def test_alexnet(self):
        from paddle_tpu.vision.models import alexnet
        pt.seed(0)
        self._check(alexnet(num_classes=10))

    def test_squeezenet(self):
        from paddle_tpu.vision.models import squeezenet1_1
        pt.seed(0)
        self._check(squeezenet1_1(num_classes=10))

    def test_mobilenet_v1_v2(self):
        from paddle_tpu.vision.models import mobilenet_v1, mobilenet_v2
        pt.seed(0)
        self._check(mobilenet_v1(scale=0.25, num_classes=10))
        self._check(mobilenet_v2(scale=0.25, num_classes=10))

    def test_densenet121(self):
        from paddle_tpu.vision.models import densenet121
        pt.seed(0)
        self._check(densenet121(num_classes=10))

    def test_relu6_hardswish(self):
        import jax.numpy as jnp
        from paddle_tpu.nn import functional as F
        x = jnp.array([-4.0, -1.0, 0.0, 3.0, 7.0])
        np.testing.assert_allclose(F.relu6(x), [0, 0, 0, 3, 6])
        np.testing.assert_allclose(
            F.hardswish(x), x * np.clip(np.asarray(x) + 3, 0, 6) / 6)


class TestVersionAndModes:
    def test_version_module(self):
        assert pt.version.full_version == pt.__version__
        assert pt.version.cuda() is False

    def test_static_mode_toggles(self):
        assert pt.in_dynamic_mode()
        pt.enable_static()
        try:
            assert not pt.in_dynamic_mode()
        finally:
            pt.disable_static()
        assert pt.in_dynamic_mode()


class TestVisionModelTail:
    """Round-2 vision families (reference:
    python/paddle/vision/models/{resnet,shufflenetv2,googlenet}.py)."""

    def _run(self, model, size=64):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.layer import functional_call, raw_params
        x = jnp.zeros((1, 3, size, size))
        model.eval()
        out = jax.jit(lambda p: functional_call(model, p, x))(
            raw_params(model))      # one program, not one an op
        assert out.shape == (1, 10)
        return model

    def test_resnext_and_wide_resnet(self):
        from paddle_tpu.vision.models import (resnext50_32x4d,
                                              wide_resnet50_2)
        pt.seed(0)
        rx = self._run(resnext50_32x4d(num_classes=10))
        # grouped 3x3: weight in-channel dim is width/groups
        w = rx.layer1[0].conv2.weight
        assert w.shape[1] * 32 == w.shape[0]
        wr = self._run(wide_resnet50_2(num_classes=10))
        assert wr.layer1[0].conv2.weight.shape[0] == 128  # 2x width

    def test_shufflenet_v2(self):
        from paddle_tpu.vision.models import shufflenet_v2_x0_5
        pt.seed(0)
        m = self._run(shufflenet_v2_x0_5(num_classes=10))
        n = sum(int(np.prod(p.shape)) for p in m.parameters())
        assert n < 1.5e6  # x0.5 is the sub-1.5M-param preset

    def test_googlenet(self):
        from paddle_tpu.vision.models import googlenet
        pt.seed(0)
        m = self._run(googlenet(num_classes=10))
        n = sum(int(np.prod(p.shape)) for p in m.parameters())
        assert 5e6 < n < 8e6  # inception-v1 backbone scale

    def test_resnext_needs_bottleneck(self):
        import pytest
        from paddle_tpu.vision.models import ResNet
        with pytest.raises(ValueError, match="bottleneck"):
            ResNet(18, groups=32, width_per_group=4)


class TestTopLevelParityRound2:
    def test_places_and_tensor_aliases(self):
        import jax.numpy as jnp
        assert repr(pt.CPUPlace()) == "CPUPlace()"
        assert "Place(0)" in repr(pt.CUDAPlace(0))   # accelerator = TPU
        t = pt.tensor([1.0, 2.0])
        assert pt.is_tensor(t) and not pt.is_tensor("x")
        assert pt.iinfo("int32").max == 2**31 - 1
        assert pt.finfo("float32").eps > 0

    def test_rng_state_roundtrip(self):
        pt.seed(7)
        _ = pt.randn([3])
        state = pt.get_rng_state()
        a = np.asarray(pt.randn([4]))
        pt.set_rng_state(state)
        b = np.asarray(pt.randn([4]))
        np.testing.assert_array_equal(a, b)

    def test_grad_enabled_flag(self):
        assert pt.is_grad_enabled()
        with pt.no_grad():
            assert not pt.is_grad_enabled()
        with pt.set_grad_enabled(False):
            assert not pt.is_grad_enabled()
        assert pt.is_grad_enabled()

    def test_incubate_top_level(self):
        from paddle_tpu import incubate
        assert hasattr(incubate, "LookAhead")
        assert hasattr(incubate, "ModelAverage")


class TestStaticRound2:
    def test_gradients_and_append_backward(self):
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (3,), "float32")
            y = prog.data("y", (3,), "float32")
            z = (x * y + x.exp()).sum()
            gx, gy = static.gradients(z, [x, y])
        exe = static.Executor()
        xv = np.array([0.1, 0.2, 0.3], np.float32)
        yv = np.array([1.0, 2.0, 3.0], np.float32)
        _, g1, g2 = exe.run(prog, feed={"x": xv, "y": yv},
                            fetch_list=[z, gx, gy])
        np.testing.assert_allclose(g1, yv + np.exp(xv), rtol=1e-5)
        np.testing.assert_allclose(g2, xv, rtol=1e-6)
        pairs = static.append_backward(z)
        assert [v.name for v, _ in pairs] == ["x", "y"]

    def test_scope_guard(self):
        from paddle_tpu import static
        sc = static.Scope()
        with static.scope_guard(sc):
            static.global_scope().set_var("a", 1)
            assert static.global_scope().find_var("a") == 1
        assert static.global_scope().find_var("a") is None

    def test_save_load_inference_model(self, tmp_path):
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (4,), "float32")
            out = (x * 2.0 + 1.0).sum()
        exe = static.Executor()
        path = str(tmp_path / "inf")
        static.save_inference_model(path, [x], [out], exe)
        prog2, feeds, fetches = static.load_inference_model(path, exe)
        xv = np.arange(4, dtype=np.float32)
        ref = exe.run(prog, feed={"x": xv}, fetch_list=[out])
        got = exe.run(prog2, feed={"x": xv}, fetch_list=fetches)
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-6)

    def test_gradients_wrt_intermediate(self):
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (3,), "float32")
            h = x * 2.0
            z = (h * h).sum()
            (gh,) = static.gradients(z, [h])
        exe = static.Executor()
        xv = np.array([1.0, 2.0, 3.0], np.float32)
        (g,) = exe.run(prog, feed={"x": xv}, fetch_list=[gh])
        np.testing.assert_allclose(g, 2 * (2 * xv), rtol=1e-6)  # dz/dh = 2h

    def test_set_grad_enabled_imperative(self):
        pt.set_grad_enabled(False)
        assert not pt.is_grad_enabled()
        pt.set_grad_enabled(True)
        assert pt.is_grad_enabled()

    def test_place_isinstance_and_to_tensor_bridge(self):
        t = pt.to_tensor([1.0, 2.0], place=pt.CPUPlace())
        assert pt.is_tensor(t)
        assert isinstance(pt.CUDAPlace(0), pt.CUDAPlace)
        assert isinstance(pt.CPUPlace(), pt.CPUPlace)
        t2 = pt.tensor([3.0], place=pt.CUDAPlace(0))
        assert pt.is_tensor(t2)


class TestVisionModelsTail3:
    """Round-3 model zoo tail (reference:
    python/paddle/vision/models/{mobilenetv3,inceptionv3,lenet}.py)."""

    _check = TestVisionZoo.__dict__["_check"]

    def test_mobilenet_v3(self):
        from paddle_tpu.vision.models import (mobilenet_v3_large,
                                              mobilenet_v3_small)
        pt.seed(0)
        self._check(mobilenet_v3_small(scale=0.5, num_classes=10))
        self._check(mobilenet_v3_large(scale=0.35, num_classes=10))

    def test_inception_v3(self):
        from paddle_tpu.vision.models import inception_v3
        pt.seed(0)
        self._check(inception_v3(num_classes=10), in_shape=(1, 3, 96, 96))

    def test_lenet_factory(self):
        import jax.numpy as jnp
        from paddle_tpu.vision.models import lenet
        pt.seed(0)
        m = lenet(num_classes=10)
        assert m(jnp.ones((2, 1, 28, 28))).shape == (2, 10)


class TestOpsOnStaticVars:
    """Round-3: dynamic paddle_tpu.ops / nn.functional callables accept
    static.Var placeholders directly (VERDICT r2 weak #6 — previously
    static-graph code had to be rewritten to Var methods/static.apply)."""

    def test_dynamic_ops_record_on_vars(self):
        import numpy as np
        import paddle_tpu.nn.functional as F
        from paddle_tpu import static

        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", (4, 8), "float32")
            h = pt.add(pt.matmul(x, pt.ones((8, 3))), 0.0)  # ufunc path
            h = F.relu(h)                                   # custom_jvp path
            h = F.softmax(h, axis=-1)
            s = pt.sum(h, axis=-1)
        exe = static.Executor()
        xv = np.random.default_rng(0).standard_normal((4, 8)) \
            .astype("float32")
        out = exe.run(prog, feed={"x": xv}, fetch_list=[s])[0]
        np.testing.assert_allclose(out, np.ones(4, np.float32), rtol=1e-5)

    def test_gradients_through_dynamic_ops(self):
        import numpy as np
        from paddle_tpu import static

        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", (3, 3), "float32")
            y = pt.sum(pt.tanh(x) * 2.0)
            (gx,) = static.gradients([y], [x])
        exe = static.Executor()
        xv = np.random.default_rng(1).standard_normal((3, 3)) \
            .astype("float32")
        g = exe.run(prog, feed={"x": xv}, fetch_list=[gx])[0]
        np.testing.assert_allclose(g, 2.0 * (1 - np.tanh(xv) ** 2),
                                   rtol=1e-5)

    def test_eager_calls_unaffected(self):
        import jax.numpy as jnp
        import numpy as np
        out = pt.add(jnp.ones(3), jnp.ones(3))
        np.testing.assert_allclose(np.asarray(out), 2.0)
