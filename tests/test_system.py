"""Top-level sanity: public API imports and the registry is complete."""
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable


def test_ten_archs_registered():
    assert len(ARCH_IDS) == 11


def test_every_cell_defined():
    """44 (arch x shape) cells: each is either applicable or a documented
    skip with a reason."""
    n_app, n_skip = 0, 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            ok, reason = shape_applicable(cfg, shape)
            if ok:
                n_app += 1
            else:
                assert reason
                n_skip += 1
    assert n_app + n_skip == 44
    assert n_skip == 9  # long_500k for the 9 archs with full attention


def test_smoke_configs_are_small():
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        assert cfg.d_model <= 128 and cfg.n_layers <= 12
