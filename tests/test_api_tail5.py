"""Round-4 second breadth pass: vision datasets/models tail, fleet role
surface, quantization base classes, ReduceLROnPlateau, jit conversion
controls + TranslatedLayer, amp capability probes.
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as P
import paddle_tpu.jit as J


class TestVisionDatasets:
    def test_fashion_mnist_is_mnist_format(self, tmp_path):
        import gzip
        import struct

        from paddle_tpu.vision.datasets import MNIST, FashionMNIST
        imgs = np.arange(2 * 28 * 28, dtype=np.uint8).reshape(2, 28, 28)
        ip = tmp_path / "img.gz"
        lp = tmp_path / "lab.gz"
        with gzip.open(ip, "wb") as f:
            f.write(struct.pack(">IIII", 2051, 2, 28, 28) + imgs.tobytes())
        with gzip.open(lp, "wb") as f:
            f.write(struct.pack(">II", 2049, 2) + bytes([3, 7]))
        ds = FashionMNIST(str(ip), str(lp))
        assert isinstance(ds, MNIST) and len(ds) == 2
        img, lab = ds[1]
        assert img.shape == (28, 28) and lab == 7

    def test_cifar100_fine_labels(self, tmp_path):
        import pickle

        from paddle_tpu.vision.datasets import Cifar100
        data = {b"data": np.zeros((3, 3072), np.uint8),
                b"fine_labels": [5, 17, 99]}
        with open(tmp_path / "train", "wb") as f:
            pickle.dump(data, f)
        ds = Cifar100(str(tmp_path), mode="train")
        img, lab = ds[2]
        assert img.shape == (3, 32, 32) and lab == 99

    def test_dataset_folder_and_image_folder(self, tmp_path):
        from PIL import Image

        from paddle_tpu.vision.datasets import DatasetFolder, ImageFolder
        for cls, n in (("cat", 2), ("dog", 1)):
            d = tmp_path / cls
            d.mkdir()
            for i in range(n):
                Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(
                    d / f"{i}.png")
        ds = DatasetFolder(str(tmp_path))
        assert ds.classes == ["cat", "dog"] and len(ds) == 3
        img, lab = ds[0]
        assert img.shape == (4, 4, 3) and lab == 0
        flat = ImageFolder(str(tmp_path))
        assert len(flat) == 3
        (img,) = flat[0]
        assert img.shape == (4, 4, 3)

    def test_voc2012_pairs(self, tmp_path):
        from PIL import Image

        from paddle_tpu.vision.datasets import VOC2012
        base = tmp_path
        (base / "ImageSets" / "Segmentation").mkdir(parents=True)
        (base / "JPEGImages").mkdir()
        (base / "SegmentationClass").mkdir()
        (base / "ImageSets" / "Segmentation" / "train.txt").write_text(
            "s1\n")
        Image.fromarray(np.zeros((6, 6, 3), np.uint8)).save(
            base / "JPEGImages" / "s1.jpg")
        Image.fromarray(np.ones((6, 6), np.uint8)).save(
            base / "SegmentationClass" / "s1.png")
        ds = VOC2012(str(base), mode="train")
        img, mask = ds[0]
        assert img.shape == (6, 6, 3) and mask.shape == (6, 6)

    def test_densenet_variants(self):
        from paddle_tpu.vision.models import (densenet161, densenet169,
                                              densenet201)
        import jax
        from paddle_tpu.nn.layer import functional_call, raw_params
        m = densenet169(num_classes=7)
        x = jnp.zeros((1, 3, 32, 32))
        out = jax.jit(lambda p: functional_call(m, p, x))(raw_params(m))
        assert out.shape == (1, 7)      # one program, not one an op
        assert callable(densenet161) and callable(densenet201)


class TestFleetRoleSurface:
    def test_worker_introspection(self):
        import paddle_tpu.distributed.fleet as fleet
        assert fleet.worker_index() == 0
        assert fleet.worker_num() >= 1
        assert fleet.is_first_worker()
        assert fleet.server_num() == 0 and fleet.server_index() == -1
        fleet.barrier_worker()

    def test_endpoints_from_env(self, monkeypatch):
        import paddle_tpu.distributed.fleet as fleet
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1,b:2")
        assert fleet.worker_endpoints() == ["a:1", "b:2"]
        assert fleet.worker_endpoints(to_string=True) == "a:1,b:2"

    def test_user_defined_role_maker(self):
        import paddle_tpu.distributed.fleet as fleet
        r = fleet.UserDefinedRoleMaker(current_id=1, role="server",
                                       worker_num=2,
                                       server_endpoints=["a:1", "b:2"])
        assert r.is_server() and not r.is_worker() and r.server_id == 1

    def test_util_base(self):
        import paddle_tpu.distributed.fleet as fleet
        u = fleet.UtilBase()
        out = u.all_reduce(np.asarray([1.0, 2.0]))
        np.testing.assert_allclose(out, [1.0, 2.0])  # world 1
        gathered = u.all_gather({"k": 1})
        assert gathered and gathered[0] == {"k": 1}
        u.barrier()


class TestQuantizationBases:
    def test_base_classes_and_registry(self):
        import paddle_tpu.quantization as Q
        assert issubclass(Q.FakeQuanterWithAbsMax, P.nn.Layer)

        @Q.quanter("TestQuanter")
        class TQ(Q.BaseQuanter):
            def forward(self, x):
                return x

            def scales(self):
                return jnp.ones(())

        assert Q._QUANTER_REGISTRY["TestQuanter"] is TQ
        t = TQ()
        assert t.bit_length() == 8 and t.zero_points() is None


class TestReduceLROnPlateau:
    def test_reduces_after_patience(self):
        import paddle_tpu.callbacks as C

        class FakeOpt:
            lr = 0.1

            def get_lr(self):
                return self.lr

            def set_lr(self, v):
                self.lr = v

        class FakeModel:
            _optimizer = FakeOpt()

        cb = C.ReduceLROnPlateau(patience=1, factor=0.5, verbose=0)
        m = FakeModel()
        cb.set_model(m)
        cb.on_epoch_end(0, {"loss": 1.0})
        cb.on_epoch_end(1, {"loss": 0.5})   # improved
        cb.on_epoch_end(2, {"loss": 0.5})   # patience=1 bad epoch: reduce
        assert abs(m._optimizer.lr - 0.05) < 1e-9

    def test_min_lr_floor(self):
        import paddle_tpu.callbacks as C

        class FakeOpt:
            lr = 1e-5

            def get_lr(self):
                return self.lr

            def set_lr(self, v):
                self.lr = v

        class FakeModel:
            _optimizer = FakeOpt()

        cb = C.ReduceLROnPlateau(patience=0, factor=0.1, min_lr=1e-5,
                                 verbose=0)
        m = FakeModel()
        cb.set_model(m)
        cb.on_epoch_end(0, {"loss": 1.0})
        cb.on_epoch_end(1, {"loss": 1.0})   # patience=0: first bad epoch
        assert m._optimizer.lr == 1e-5      # reduces, floored at min_lr


class TestJitControls:
    def test_enable_to_static_toggle(self):
        J.enable_to_static(False)
        try:
            @J.to_static
            def f(x):
                return x + 1
            # passthrough: the raw function, no jit wrapper
            assert f.__name__ == "f"
        finally:
            J.enable_to_static(True)

    def test_not_to_static_marker(self):
        @J.not_to_static
        def f(x):
            return x

        assert f._pdtpu_not_to_static
        g = J.to_static(f)
        assert g is f  # stays eager

    def test_ignore_module(self):
        mods = J.ignore_module(os)
        assert "os" in mods

    def test_save_load_translated_layer(self, tmp_path):
        m = P.nn.Linear(4, 3)
        path = str(tmp_path / "m")
        J.save(m, path, input_spec=[J.InputSpec([2, 4])])
        loaded = J.load(path)
        assert isinstance(loaded, J.TranslatedLayer)
        out = loaded(jnp.ones((2, 4)))
        res = out[0] if isinstance(out, (list, tuple)) else out
        assert res.shape == (2, 3)
        assert loaded.eval() is loaded
        with pytest.raises(RuntimeError, match="inference artifact"):
            loaded.train()

    def test_onnx_export_writes_aot_artifact(self, tmp_path):
        import paddle_tpu.onnx as onnx
        m = P.nn.Linear(4, 4)
        p = str(tmp_path / "m")
        onnx.export(m, p, input_spec=[J.InputSpec([1, 4])])
        assert os.path.exists(p + ".stablehlo")
        with pytest.raises(NotImplementedError, match="de-scoped"):
            onnx.export(m, str(tmp_path / "m.onnx"))


class TestAmpProbes:
    def test_capability_probes(self):
        import paddle_tpu.amp as A
        assert A.is_bfloat16_supported() is True
        assert A.is_float16_supported() is True


class TestReviewFixesTail5:
    def test_enable_to_static_is_call_time(self):
        calls = []

        @J.to_static
        def f(x):
            calls.append(1)
            return x + 1

        f(jnp.zeros(2))          # compiled path
        J.enable_to_static(False)
        try:
            out = f(jnp.ones(2))  # routes to eager NOW (reference flow)
            np.testing.assert_allclose(np.asarray(out), 2.0)
            assert calls  # eager body actually ran
        finally:
            J.enable_to_static(True)

    def test_ignore_module_skips_sot(self):
        import types

        import jax as _jax
        mod = types.ModuleType("pdtpu_test_ignored_mod")
        J.ignore_module(mod)

        def branchy(x):
            if x.sum() > 0:
                y = x
            else:
                y = -x
            return y

        # un-ignored: SOT converts the bare `if` -> compiles and runs
        ok = J.to_static(branchy, convert_control_flow=True)
        np.testing.assert_allclose(np.asarray(ok(jnp.ones(3))), 1.0)

        # same source, module marked ignored: SOT skipped -> the
        # data-dependent `if` graph-breaks exactly as without SOT
        def branchy2(x):
            if x.sum() > 0:
                y = x
            else:
                y = -x
            return y

        branchy2.__module__ = "pdtpu_test_ignored_mod"
        g = J.to_static(branchy2, convert_control_flow=True)
        with pytest.raises((J.GraphBreakError,
                            _jax.errors.TracerBoolConversionError)):
            g(jnp.ones(3))

    def test_user_defined_role_maker_activates_ps(self):
        import paddle_tpu.distributed.fleet as fleet
        fleet._reset()
        try:
            rt = fleet.init(fleet.UserDefinedRoleMaker(
                current_id=0, role="server", worker_num=1,
                server_endpoints=["127.0.0.1:0"]), is_collective=False)
            assert fleet.is_server()
            assert not fleet.is_worker()
            assert rt is not None
        finally:
            fleet._reset()

    def test_utilbase_mode_validated(self):
        import paddle_tpu.distributed.fleet as fleet
        u = fleet.UtilBase()
        np.testing.assert_allclose(u.all_reduce(np.asarray([2.0]), "max"),
                                   [2.0])
        with pytest.raises(ValueError, match="sum/max/min"):
            u.all_reduce(np.asarray([1.0]), mode="mean")


class TestFusedMoeAndPlace:
    def test_fused_moe_matches_manual(self):
        from paddle_tpu.incubate.nn import functional as IF
        rng = np.random.RandomState(0)
        H, I, E = 8, 16, 4
        x = jnp.asarray(rng.randn(2, 3, H).astype(np.float32))
        gw = jnp.asarray(rng.randn(H, E).astype(np.float32))
        w1 = jnp.asarray(rng.randn(E, H, 2 * I).astype(np.float32) / 4)
        w2 = jnp.asarray(rng.randn(E, I, H).astype(np.float32) / 4)
        out = IF.fused_moe(x, gw, w1, w2, moe_topk=2)
        assert out.shape == x.shape
        t = np.asarray(x).reshape(-1, H)
        logits = t @ np.asarray(gw)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.zeros_like(t)
        for n in range(t.shape[0]):
            idx = np.argsort(-p[n])[:2]
            wsum = p[n][idx].sum()
            for e in idx:
                h1 = t[n] @ np.asarray(w1)[e]
                g, u = h1[:I], h1[I:]
                act = (g / (1 + np.exp(-g))) * u
                want[n] += (p[n][e] / wsum) * (act @ np.asarray(w2)[e])
        np.testing.assert_allclose(np.asarray(out).reshape(-1, H), want,
                                   atol=2e-5)

    def test_fused_moe_jits(self):
        import jax as _jax

        from paddle_tpu.incubate.nn import functional as IF
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 8).astype(np.float32))
        gw = jnp.asarray(rng.randn(8, 2).astype(np.float32))
        w1 = jnp.asarray(rng.randn(2, 8, 8).astype(np.float32))
        w2 = jnp.asarray(rng.randn(2, 4, 8).astype(np.float32))
        f = _jax.jit(lambda a: IF.fused_moe(a, gw, w1, w2, moe_topk=1))
        assert f(x).shape == x.shape

    def test_tensor_place_property(self):
        import jax as _jax
        x = P.to_tensor([1.0])
        from paddle_tpu.device import CPUPlace, TPUPlace
        assert isinstance(x.place, (CPUPlace, TPUPlace))

        @_jax.jit
        def f(v):
            assert v.place is not None  # tracer path
            return v

        f(x)


class TestDeviceCuda:
    def test_stats_api_surface(self):
        import paddle_tpu.device.cuda as C
        assert C.device_count() >= 1
        assert isinstance(C.get_device_name(), str)
        # stats are >= 0 (0 on backends whose PJRT reports none)
        assert C.memory_allocated() >= 0
        assert C.max_memory_allocated() >= C.memory_allocated() or \
            C.max_memory_allocated() == 0
        assert C.memory_reserved() >= 0
        props = C.get_device_properties()
        assert hasattr(props, "total_memory") and hasattr(props, "name")
        cap = C.get_device_capability()
        assert isinstance(cap, tuple) and len(cap) == 2
        C.empty_cache()
        with C.stream_guard(C.current_stream()):
            pass

    def test_lazy_module_attr(self):
        import paddle_tpu.device as D
        assert D.cuda.device_count() >= 1
