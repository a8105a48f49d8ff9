"""Smoke tier for the generative and unsupervised examples (ref: the
reference's example/gan, example/autoencoder, example/vae-gan,
example/neural-style, example/restricted-boltzmann-machine,
example/deep-embedded-clustering, example/adversary). Each runs end to
end with tiny settings and asserts its learning signal."""


def test_gan_example_moves_toward_manifold(load_example):
    d0, d1 = load_example("gan/dcgan.py").main(["--steps", "150"])
    assert d1 < d0 * 0.8, f"generator did not improve: {d0} -> {d1}"


def test_autoencoder_example(load_example):
    first, last = load_example("autoencoder/train_ae.py").main(
        ["--steps", "120"])
    assert last < first * 0.7


def test_adversary_fgsm_example(load_example):
    clean, adv = load_example("adversary/fgsm.py").main(["--steps", "120"])
    assert clean > 0.9 and adv < clean - 0.3


def test_neural_style_example_optimizes_pixels(load_example):
    first, last = load_example("neural_style/neural_style.py").main(
        ["--steps", "60"])
    assert last < first * 0.3


def test_dec_clustering_example(load_example):
    acc = load_example("deep_embedded_clustering/dec.py").main([])
    assert acc > 0.9  # well-separated blobs


def test_rbm_cd1_example(load_example):
    first, last = load_example("restricted_boltzmann_machine/rbm.py").main(
        ["--steps", "200"])
    assert last < first * 0.5


def test_vae_gan_example_trains(load_example):
    first, last = load_example("vae_gan/vae_gan.py").main(["--steps", "150"])
    assert last < first * 0.85
