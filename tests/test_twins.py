"""The whole-model cases (``tests/twins.py``) of the two twins whose family
files are the longest of tier-1 (``test_latent_moe_model.py``,
``test_ssm_moe_model.py``): under ``--dist loadfile`` a file runs on one
worker and the run is as long as its longest file, so these run from a file
of their own.  The other twins' cases run from their family's file."""

from twins import (  # noqa: F401
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_the_shares_add_up_to_the_uncut_layer,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32)

TWINS = ("tiny-xing", "tiny-twotower")
