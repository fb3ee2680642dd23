"""What ``tests/conftest.py`` restores between tests. The pairs below
rely on running in file order in one process, which ``--dist loadfile``
keeps."""
import paddle_tpu.static as static


def test_static_mode_left_on_by_a_test():
    static.enable_static()
    assert static.in_static_mode()


def test_static_mode_is_off_for_the_next_test():
    assert static.in_dynamic_mode()
