// GENERATED by cpp-package/OpWrapperGenerator.py — do not edit.
// One typed wrapper per registered operator over the generic
// Operator builder (see ndarray.hpp).
#ifndef MXNET_TPU_CPP_OPS_HPP_
#define MXNET_TPU_CPP_OPS_HPP_

#include <string>
#include <utility>
#include <vector>

#include "ndarray.hpp"

namespace mxtpu {
namespace cpp {
namespace op {


// L2Normalization(data; attrs: eps, mode)
inline std::vector<NDArray> L2Normalization(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("L2Normalization");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// _sym_index(data; attrs: index_spec)
inline std::vector<NDArray> _sym_index(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("_sym_index");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// abs(x)
inline std::vector<NDArray> abs(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("abs");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// activation(data; attrs: act_type)
inline std::vector<NDArray> activation(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("activation");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// all_finite(data; attrs: init_output)
inline std::vector<NDArray> all_finite(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("all_finite");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// allclose(a, b; attrs: rtol, atol, equal_nan)
inline std::vector<NDArray> allclose(
    const NDArray &a, const NDArray &b,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("allclose");
  op_.AddInput(a);
  op_.AddInput(b);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// amp_cast(data; attrs: dtype)
inline std::vector<NDArray> amp_cast(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("amp_cast");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arccos(x)
inline std::vector<NDArray> arccos(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arccos");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arccosh(x)
inline std::vector<NDArray> arccosh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arccosh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arcsin(x)
inline std::vector<NDArray> arcsin(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arcsin");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arcsinh(x)
inline std::vector<NDArray> arcsinh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arcsinh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arctan(x)
inline std::vector<NDArray> arctan(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arctan");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// arctanh(x)
inline std::vector<NDArray> arctanh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("arctanh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// argmax(data; attrs: axis, keepdims)
inline std::vector<NDArray> argmax(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("argmax");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// argmax_channel(data)
inline std::vector<NDArray> argmax_channel(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("argmax_channel");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// argmin(data; attrs: axis, keepdims)
inline std::vector<NDArray> argmin(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("argmin");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// argsort(data; attrs: axis, is_ascend, dtype)
inline std::vector<NDArray> argsort(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("argsort");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// batch_dot(lhs, rhs; attrs: transpose_a, transpose_b)
inline std::vector<NDArray> batch_dot(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("batch_dot");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// batch_norm(data, gamma, beta, moving_mean, moving_var; attrs: eps, momentum, fix_gamma, use_global_stats, output_mean_var, axis, cudnn_off)
inline std::vector<NDArray> batch_norm(
    const NDArray &data, const NDArray &gamma, const NDArray &beta, const NDArray &moving_mean, const NDArray &moving_var,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("batch_norm");
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  op_.AddInput(moving_mean);
  op_.AddInput(moving_var);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// batch_take(a, indices)
inline std::vector<NDArray> batch_take(
    const NDArray &a, const NDArray &indices,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("batch_take");
  op_.AddInput(a);
  op_.AddInput(indices);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// bilinear_resize_2d(data; attrs: like, height, width, scale_height, scale_width, mode, align_corners)
inline std::vector<NDArray> bilinear_resize_2d(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("bilinear_resize_2d");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// bilinear_sampler(data, grid; attrs: cudnn_off)
inline std::vector<NDArray> bilinear_sampler(
    const NDArray &data, const NDArray &grid,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("bilinear_sampler");
  op_.AddInput(data);
  op_.AddInput(grid);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// bipartite_matching(data; attrs: threshold, is_ascend, topk)
inline std::vector<NDArray> bipartite_matching(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("bipartite_matching");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// boolean_mask(data, index; attrs: axis)
inline std::vector<NDArray> boolean_mask(
    const NDArray &data, const NDArray &index,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("boolean_mask");
  op_.AddInput(data);
  op_.AddInput(index);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// box_iou(lhs, rhs; attrs: format)
inline std::vector<NDArray> box_iou(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("box_iou");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// box_nms(data; attrs: overlap_thresh, valid_thresh, topk, coord_start, score_index, id_index, background_id, force_suppress, in_format, out_format)
inline std::vector<NDArray> box_nms(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("box_nms");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// broadcast_axis(data; attrs: axis, size)
inline std::vector<NDArray> broadcast_axis(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("broadcast_axis");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// broadcast_like(lhs, rhs)
inline std::vector<NDArray> broadcast_like(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("broadcast_like");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// broadcast_to(data; attrs: shape)
inline std::vector<NDArray> broadcast_to(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("broadcast_to");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cast(data; attrs: dtype)
inline std::vector<NDArray> cast(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cast");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cast_storage(data; attrs: stype)
inline std::vector<NDArray> cast_storage(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cast_storage");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cbrt(x)
inline std::vector<NDArray> cbrt(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cbrt");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// ceil(x)
inline std::vector<NDArray> ceil(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("ceil");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// clip(data; attrs: a_min, a_max)
inline std::vector<NDArray> clip(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("clip");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// convolution(data, weight; attrs: bias, kernel, stride, dilate, pad, num_filter, num_group, no_bias, cudnn_tune, cudnn_off, workspace, layout)
inline std::vector<NDArray> convolution(
    const NDArray &data, const NDArray &weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("convolution");
  op_.AddInput(data);
  op_.AddInput(weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// correlation(data1, data2; attrs: kernel_size, max_displacement, stride1, stride2, pad_size, is_multiply)
inline std::vector<NDArray> correlation(
    const NDArray &data1, const NDArray &data2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("correlation");
  op_.AddInput(data1);
  op_.AddInput(data2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cos(x)
inline std::vector<NDArray> cos(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cos");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cosh(x)
inline std::vector<NDArray> cosh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cosh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// ctc_loss(pred, label; attrs: pred_lengths, label_lengths)
inline std::vector<NDArray> ctc_loss(
    const NDArray &pred, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("ctc_loss");
  op_.AddInput(pred);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cumprod(a; attrs: axis, dtype)
inline std::vector<NDArray> cumprod(
    const NDArray &a,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cumprod");
  op_.AddInput(a);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// cumsum(a; attrs: axis, dtype)
inline std::vector<NDArray> cumsum(
    const NDArray &a,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("cumsum");
  op_.AddInput(a);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// deconvolution(data, weight; attrs: bias, kernel, stride, dilate, pad, adj, target_shape, num_filter, num_group, no_bias, workspace, cudnn_tune, cudnn_off, layout)
inline std::vector<NDArray> deconvolution(
    const NDArray &data, const NDArray &weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("deconvolution");
  op_.AddInput(data);
  op_.AddInput(weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// deformable_convolution(data, offset, weight; attrs: bias, kernel, stride, dilate, pad, num_filter, num_group, num_deformable_group, no_bias, workspace, layout)
inline std::vector<NDArray> deformable_convolution(
    const NDArray &data, const NDArray &offset, const NDArray &weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("deformable_convolution");
  op_.AddInput(data);
  op_.AddInput(offset);
  op_.AddInput(weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// degrees(x)
inline std::vector<NDArray> degrees(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("degrees");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// depth_to_space(data; attrs: block_size)
inline std::vector<NDArray> depth_to_space(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("depth_to_space");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// dequantize(data, min_range, max_range; attrs: out_type)
inline std::vector<NDArray> dequantize(
    const NDArray &data, const NDArray &min_range, const NDArray &max_range,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("dequantize");
  op_.AddInput(data);
  op_.AddInput(min_range);
  op_.AddInput(max_range);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// diag(data; attrs: k)
inline std::vector<NDArray> diag(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("diag");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// digamma(data)
inline std::vector<NDArray> digamma(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("digamma");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// dot(lhs, rhs; attrs: transpose_a, transpose_b)
inline std::vector<NDArray> dot(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("dot");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// dropout(data; attrs: p, mode, axes, cudnn_off)
inline std::vector<NDArray> dropout(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("dropout");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// elemwise_div(x1, x2)
inline std::vector<NDArray> elemwise_div(
    const NDArray &x1, const NDArray &x2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("elemwise_div");
  op_.AddInput(x1);
  op_.AddInput(x2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// embedding(data, weight; attrs: input_dim, output_dim, dtype, sparse_grad)
inline std::vector<NDArray> embedding(
    const NDArray &data, const NDArray &weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("embedding");
  op_.AddInput(data);
  op_.AddInput(weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// equal(lhs, rhs)
inline std::vector<NDArray> equal(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("equal");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// erf(x)
inline std::vector<NDArray> erf(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("erf");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// erfinv(x)
inline std::vector<NDArray> erfinv(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("erfinv");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// exp(x)
inline std::vector<NDArray> exp(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("exp");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// expand_dims(data; attrs: axis)
inline std::vector<NDArray> expand_dims(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("expand_dims");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// expm1(x)
inline std::vector<NDArray> expm1(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("expm1");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// flatten(data)
inline std::vector<NDArray> flatten(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("flatten");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// floor(x)
inline std::vector<NDArray> floor(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("floor");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// full_like(data; attrs: fill_value)
inline std::vector<NDArray> full_like(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("full_like");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// fully_connected(data, weight; attrs: bias, num_hidden, no_bias, flatten)
inline std::vector<NDArray> fully_connected(
    const NDArray &data, const NDArray &weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("fully_connected");
  op_.AddInput(data);
  op_.AddInput(weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// gamma(x)
inline std::vector<NDArray> gamma(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("gamma");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// gammaln(x)
inline std::vector<NDArray> gammaln(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("gammaln");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// gather_nd(data, indices)
inline std::vector<NDArray> gather_nd(
    const NDArray &data, const NDArray &indices,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("gather_nd");
  op_.AddInput(data);
  op_.AddInput(indices);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// gelu(data; attrs: approximate)
inline std::vector<NDArray> gelu(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("gelu");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// greater(lhs, rhs)
inline std::vector<NDArray> greater(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("greater");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// greater_equal(lhs, rhs)
inline std::vector<NDArray> greater_equal(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("greater_equal");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// grid_generator(data; attrs: transform_type, target_shape)
inline std::vector<NDArray> grid_generator(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("grid_generator");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// group_norm(data, gamma, beta; attrs: num_groups, eps)
inline std::vector<NDArray> group_norm(
    const NDArray &data, const NDArray &gamma, const NDArray &beta,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("group_norm");
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// hard_sigmoid(data; attrs: alpha, beta)
inline std::vector<NDArray> hard_sigmoid(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("hard_sigmoid");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// hypot(x1, x2)
inline std::vector<NDArray> hypot(
    const NDArray &x1, const NDArray &x2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("hypot");
  op_.AddInput(x1);
  op_.AddInput(x2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// identity(data)
inline std::vector<NDArray> identity(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("identity");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// index_add(old, index, new)
inline std::vector<NDArray> index_add(
    const NDArray &old, const NDArray &index, const NDArray &new_,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("index_add");
  op_.AddInput(old);
  op_.AddInput(index);
  op_.AddInput(new_);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// index_array(data; attrs: axes)
inline std::vector<NDArray> index_array(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("index_array");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// index_copy(old, index, new)
inline std::vector<NDArray> index_copy(
    const NDArray &old, const NDArray &index, const NDArray &new_,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("index_copy");
  op_.AddInput(old);
  op_.AddInput(index);
  op_.AddInput(new_);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// instance_norm(data, gamma, beta; attrs: eps)
inline std::vector<NDArray> instance_norm(
    const NDArray &data, const NDArray &gamma, const NDArray &beta,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("instance_norm");
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// interleaved_matmul_selfatt_qk(queries_keys_values; attrs: heads)
inline std::vector<NDArray> interleaved_matmul_selfatt_qk(
    const NDArray &queries_keys_values,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("interleaved_matmul_selfatt_qk");
  op_.AddInput(queries_keys_values);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// interleaved_matmul_selfatt_valatt(queries_keys_values, attention; attrs: heads)
inline std::vector<NDArray> interleaved_matmul_selfatt_valatt(
    const NDArray &queries_keys_values, const NDArray &attention,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("interleaved_matmul_selfatt_valatt");
  op_.AddInput(queries_keys_values);
  op_.AddInput(attention);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// isfinite(x)
inline std::vector<NDArray> isfinite(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("isfinite");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// isinf(x)
inline std::vector<NDArray> isinf(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("isinf");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// isnan(x)
inline std::vector<NDArray> isnan(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("isnan");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// layer_norm(data, gamma, beta; attrs: axis, eps, output_mean_var)
inline std::vector<NDArray> layer_norm(
    const NDArray &data, const NDArray &gamma, const NDArray &beta,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("layer_norm");
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// leaky_relu(data; attrs: gamma, act_type, slope, lower_bound, upper_bound)
inline std::vector<NDArray> leaky_relu(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("leaky_relu");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// lesser(lhs, rhs)
inline std::vector<NDArray> lesser(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("lesser");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// lesser_equal(lhs, rhs)
inline std::vector<NDArray> lesser_equal(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("lesser_equal");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_det(A)
inline std::vector<NDArray> linalg_det(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_det");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_extractdiag(A; attrs: offset)
inline std::vector<NDArray> linalg_extractdiag(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_extractdiag");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_extracttrian(A; attrs: offset, lower)
inline std::vector<NDArray> linalg_extracttrian(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_extracttrian");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_gelqf(A)
inline std::vector<NDArray> linalg_gelqf(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_gelqf");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_gemm(A, B, C; attrs: transpose_a, transpose_b, alpha, beta, axis)
inline std::vector<NDArray> linalg_gemm(
    const NDArray &A, const NDArray &B, const NDArray &C,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_gemm");
  op_.AddInput(A);
  op_.AddInput(B);
  op_.AddInput(C);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_gemm2(A, B; attrs: transpose_a, transpose_b, alpha, axis)
inline std::vector<NDArray> linalg_gemm2(
    const NDArray &A, const NDArray &B,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_gemm2");
  op_.AddInput(A);
  op_.AddInput(B);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_inverse(A)
inline std::vector<NDArray> linalg_inverse(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_inverse");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_makediag(A; attrs: offset)
inline std::vector<NDArray> linalg_makediag(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_makediag");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_maketrian(A; attrs: offset, lower)
inline std::vector<NDArray> linalg_maketrian(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_maketrian");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_potrf(A)
inline std::vector<NDArray> linalg_potrf(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_potrf");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_potri(A)
inline std::vector<NDArray> linalg_potri(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_potri");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_sumlogdiag(A)
inline std::vector<NDArray> linalg_sumlogdiag(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_sumlogdiag");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_syevd(A)
inline std::vector<NDArray> linalg_syevd(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_syevd");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_syrk(A; attrs: transpose, alpha)
inline std::vector<NDArray> linalg_syrk(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_syrk");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_trmm(A, B; attrs: transpose, rightside, lower, alpha)
inline std::vector<NDArray> linalg_trmm(
    const NDArray &A, const NDArray &B,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_trmm");
  op_.AddInput(A);
  op_.AddInput(B);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linalg_trsm(A, B; attrs: transpose, rightside, lower, alpha)
inline std::vector<NDArray> linalg_trsm(
    const NDArray &A, const NDArray &B,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linalg_trsm");
  op_.AddInput(A);
  op_.AddInput(B);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// linear_regression_output(data, label; attrs: grad_scale)
inline std::vector<NDArray> linear_regression_output(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("linear_regression_output");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// log(x)
inline std::vector<NDArray> log(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("log");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// log10(x)
inline std::vector<NDArray> log10(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("log10");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// log1p(x)
inline std::vector<NDArray> log1p(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("log1p");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// log2(x)
inline std::vector<NDArray> log2(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("log2");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// log_softmax(data; attrs: axis, temperature)
inline std::vector<NDArray> log_softmax(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("log_softmax");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// logical_and(lhs, rhs)
inline std::vector<NDArray> logical_and(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("logical_and");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// logical_not(x)
inline std::vector<NDArray> logical_not(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("logical_not");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// logical_or(lhs, rhs)
inline std::vector<NDArray> logical_or(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("logical_or");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// logical_xor(lhs, rhs)
inline std::vector<NDArray> logical_xor(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("logical_xor");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// logistic_regression_output(data, label; attrs: grad_scale)
inline std::vector<NDArray> logistic_regression_output(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("logistic_regression_output");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// lrn(data; attrs: alpha, beta, knorm, nsize)
inline std::vector<NDArray> lrn(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("lrn");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// mae_regression_output(data, label; attrs: grad_scale)
inline std::vector<NDArray> mae_regression_output(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("mae_regression_output");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// make_loss(data; attrs: grad_scale, valid_thresh, normalization)
inline std::vector<NDArray> make_loss(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("make_loss");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// max_axis(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> max_axis(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("max_axis");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// mean(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> mean(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("mean");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// min_axis(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> min_axis(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("min_axis");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// mod(x1, x2)
inline std::vector<NDArray> mod(
    const NDArray &x1, const NDArray &x2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("mod");
  op_.AddInput(x1);
  op_.AddInput(x2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// moe_ffn(data, gate_weight, w1, b1, w2, b2; attrs: num_experts, k, capacity_factor, activation, output_aux_loss)
inline std::vector<NDArray> moe_ffn(
    const NDArray &data, const NDArray &gate_weight, const NDArray &w1, const NDArray &b1, const NDArray &w2, const NDArray &b2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("moe_ffn");
  op_.AddInput(data);
  op_.AddInput(gate_weight);
  op_.AddInput(w1);
  op_.AddInput(b1);
  op_.AddInput(w2);
  op_.AddInput(b2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// moe_share_ffn(data, router_weight, router_bias, w13, w2; attrs: k, experts_lo, pass_rows, output_stats, scale, shared)
inline std::vector<NDArray> moe_share_ffn(
    const NDArray &data, const NDArray &router_weight, const NDArray &router_bias, const NDArray &w13, const NDArray &w2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("moe_share_ffn");
  op_.AddInput(data);
  op_.AddInput(router_weight);
  op_.AddInput(router_bias);
  op_.AddInput(w13);
  op_.AddInput(w2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// multi_head_attention(query, key, value; attrs: qkv_weight, qkv_bias, proj_weight, proj_bias, num_heads, mask, causal, impl)
inline std::vector<NDArray> multi_head_attention(
    const NDArray &query, const NDArray &key, const NDArray &value,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("multi_head_attention");
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// multibox_detection(cls_prob, loc_pred, anchor; attrs: clip, threshold, background_id, nms_threshold, force_suppress, variances, nms_topk)
inline std::vector<NDArray> multibox_detection(
    const NDArray &cls_prob, const NDArray &loc_pred, const NDArray &anchor,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("multibox_detection");
  op_.AddInput(cls_prob);
  op_.AddInput(loc_pred);
  op_.AddInput(anchor);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// multibox_prior(data; attrs: sizes, ratios, clip, steps, offsets)
inline std::vector<NDArray> multibox_prior(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("multibox_prior");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// multibox_target(anchor, label, cls_pred; attrs: overlap_threshold, ignore_label, negative_mining_ratio, negative_mining_thresh, minimum_negative_samples, variances)
inline std::vector<NDArray> multibox_target(
    const NDArray &anchor, const NDArray &label, const NDArray &cls_pred,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("multibox_target");
  op_.AddInput(anchor);
  op_.AddInput(label);
  op_.AddInput(cls_pred);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// nanprod(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> nanprod(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("nanprod");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// nansum(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> nansum(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("nansum");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// norm(data; attrs: ord, axis, keepdims)
inline std::vector<NDArray> norm(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("norm");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// not_equal(lhs, rhs)
inline std::vector<NDArray> not_equal(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("not_equal");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// one_hot(indices; attrs: depth, on_value, off_value, dtype)
inline std::vector<NDArray> one_hot(
    const NDArray &indices,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("one_hot");
  op_.AddInput(indices);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// ones_like(data)
inline std::vector<NDArray> ones_like(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("ones_like");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// pad(data; attrs: mode, pad_width, constant_value)
inline std::vector<NDArray> pad(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("pad");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// pick(data, index; attrs: axis, keepdims, mode)
inline std::vector<NDArray> pick(
    const NDArray &data, const NDArray &index,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("pick");
  op_.AddInput(data);
  op_.AddInput(index);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// pooling(data; attrs: kernel, pool_type, global_pool, stride, pad, pooling_convention, count_include_pad, cudnn_off, layout, p_value)
inline std::vector<NDArray> pooling(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("pooling");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// power(x1, x2)
inline std::vector<NDArray> power(
    const NDArray &x1, const NDArray &x2,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("power");
  op_.AddInput(x1);
  op_.AddInput(x2);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// prod(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> prod(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("prod");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// proposal(cls_prob, bbox_pred, im_info; attrs: rpn_pre_nms_top_n, rpn_post_nms_top_n, threshold, feature_stride, scales, ratios, rpn_min_size, iou_loss, output_score)
inline std::vector<NDArray> proposal(
    const NDArray &cls_prob, const NDArray &bbox_pred, const NDArray &im_info,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("proposal");
  op_.AddInput(cls_prob);
  op_.AddInput(bbox_pred);
  op_.AddInput(im_info);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// psroi_pooling(data, rois; attrs: spatial_scale, output_dim, pooled_size, group_size)
inline std::vector<NDArray> psroi_pooling(
    const NDArray &data, const NDArray &rois,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("psroi_pooling");
  op_.AddInput(data);
  op_.AddInput(rois);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// quantize(data, min_range, max_range; attrs: out_type)
inline std::vector<NDArray> quantize(
    const NDArray &data, const NDArray &min_range, const NDArray &max_range,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("quantize");
  op_.AddInput(data);
  op_.AddInput(min_range);
  op_.AddInput(max_range);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// quantize_v2(data; attrs: out_type, min_calib_range, max_calib_range)
inline std::vector<NDArray> quantize_v2(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("quantize_v2");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// quantized_conv(data, weight, bias, min_data, max_data, min_weight, max_weight; attrs: min_bias, max_bias, kernel, stride, pad, num_filter, num_group, no_bias, layout)
inline std::vector<NDArray> quantized_conv(
    const NDArray &data, const NDArray &weight, const NDArray &bias, const NDArray &min_data, const NDArray &max_data, const NDArray &min_weight, const NDArray &max_weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("quantized_conv");
  op_.AddInput(data);
  op_.AddInput(weight);
  op_.AddInput(bias);
  op_.AddInput(min_data);
  op_.AddInput(max_data);
  op_.AddInput(min_weight);
  op_.AddInput(max_weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// quantized_fully_connected(data, weight, bias, min_data, max_data, min_weight, max_weight; attrs: min_bias, max_bias, num_hidden, no_bias, flatten)
inline std::vector<NDArray> quantized_fully_connected(
    const NDArray &data, const NDArray &weight, const NDArray &bias, const NDArray &min_data, const NDArray &max_data, const NDArray &min_weight, const NDArray &max_weight,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("quantized_fully_connected");
  op_.AddInput(data);
  op_.AddInput(weight);
  op_.AddInput(bias);
  op_.AddInput(min_data);
  op_.AddInput(max_data);
  op_.AddInput(min_weight);
  op_.AddInput(max_weight);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// radians(x)
inline std::vector<NDArray> radians(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("radians");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// ravel_multi_index(data; attrs: shape)
inline std::vector<NDArray> ravel_multi_index(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("ravel_multi_index");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// rcbrt(data)
inline std::vector<NDArray> rcbrt(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("rcbrt");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// reciprocal(data)
inline std::vector<NDArray> reciprocal(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("reciprocal");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// relu(data)
inline std::vector<NDArray> relu(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("relu");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// repeat(data; attrs: repeats, axis)
inline std::vector<NDArray> repeat(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("repeat");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// requantize(data, min_range, max_range; attrs: out_type, min_calib_range, max_calib_range)
inline std::vector<NDArray> requantize(
    const NDArray &data, const NDArray &min_range, const NDArray &max_range,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("requantize");
  op_.AddInput(data);
  op_.AddInput(min_range);
  op_.AddInput(max_range);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// reshape(data; attrs: shape, reverse)
inline std::vector<NDArray> reshape(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("reshape");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// reshape_like(lhs, rhs)
inline std::vector<NDArray> reshape_like(
    const NDArray &lhs, const NDArray &rhs,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("reshape_like");
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// reverse(data; attrs: axis)
inline std::vector<NDArray> reverse(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("reverse");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// rint(x)
inline std::vector<NDArray> rint(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("rint");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// rms_norm(data, gamma; attrs: axis, eps)
inline std::vector<NDArray> rms_norm(
    const NDArray &data, const NDArray &gamma,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("rms_norm");
  op_.AddInput(data);
  op_.AddInput(gamma);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// rnn(data, parameters, state; attrs: state_cell, state_size, num_layers, mode, bidirectional, p, state_outputs, lstm_state_clip_min, lstm_state_clip_max, use_sequence_length, sequence_length, projection_size)
inline std::vector<NDArray> rnn(
    const NDArray &data, const NDArray &parameters, const NDArray &state,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("rnn");
  op_.AddInput(data);
  op_.AddInput(parameters);
  op_.AddInput(state);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// roi_align(data, rois; attrs: pooled_size, spatial_scale, sample_ratio, position_sensitive, aligned)
inline std::vector<NDArray> roi_align(
    const NDArray &data, const NDArray &rois,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("roi_align");
  op_.AddInput(data);
  op_.AddInput(rois);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// roi_pooling(data, rois; attrs: pooled_size, spatial_scale)
inline std::vector<NDArray> roi_pooling(
    const NDArray &data, const NDArray &rois,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("roi_pooling");
  op_.AddInput(data);
  op_.AddInput(rois);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// round(a; attrs: decimals, out)
inline std::vector<NDArray> round(
    const NDArray &a,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("round");
  op_.AddInput(a);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// rsqrt(data)
inline std::vector<NDArray> rsqrt(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("rsqrt");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sample_multinomial(data; attrs: shape, get_prob, dtype)
inline std::vector<NDArray> sample_multinomial(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sample_multinomial");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sample_normal(mu, sigma; attrs: shape, dtype)
inline std::vector<NDArray> sample_normal(
    const NDArray &mu, const NDArray &sigma,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sample_normal");
  op_.AddInput(mu);
  op_.AddInput(sigma);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sample_uniform(low, high; attrs: shape, dtype)
inline std::vector<NDArray> sample_uniform(
    const NDArray &low, const NDArray &high,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sample_uniform");
  op_.AddInput(low);
  op_.AddInput(high);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// scaled_dot_product_attention(query, key, value; attrs: mask, causal, scale, impl, dropout_p)
inline std::vector<NDArray> scaled_dot_product_attention(
    const NDArray &query, const NDArray &key, const NDArray &value,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("scaled_dot_product_attention");
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// scan_transformer_encoder(data, qkv_w, qkv_b, proj_w, proj_b, ffn1_w, ffn1_b, ffn2_w, ffn2_b, ln1_g, ln1_b, ln2_g, ln2_b, lnf_g, lnf_b; attrs: qkv_lora_a, qkv_lora_b, num_heads, dropout, activation, impl, causal, remat, lora_scale)
inline std::vector<NDArray> scan_transformer_encoder(
    const NDArray &data, const NDArray &qkv_w, const NDArray &qkv_b, const NDArray &proj_w, const NDArray &proj_b, const NDArray &ffn1_w, const NDArray &ffn1_b, const NDArray &ffn2_w, const NDArray &ffn2_b, const NDArray &ln1_g, const NDArray &ln1_b, const NDArray &ln2_g, const NDArray &ln2_b, const NDArray &lnf_g, const NDArray &lnf_b,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("scan_transformer_encoder");
  op_.AddInput(data);
  op_.AddInput(qkv_w);
  op_.AddInput(qkv_b);
  op_.AddInput(proj_w);
  op_.AddInput(proj_b);
  op_.AddInput(ffn1_w);
  op_.AddInput(ffn1_b);
  op_.AddInput(ffn2_w);
  op_.AddInput(ffn2_b);
  op_.AddInput(ln1_g);
  op_.AddInput(ln1_b);
  op_.AddInput(ln2_g);
  op_.AddInput(ln2_b);
  op_.AddInput(lnf_g);
  op_.AddInput(lnf_b);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// scatter_nd(data, indices; attrs: shape)
inline std::vector<NDArray> scatter_nd(
    const NDArray &data, const NDArray &indices,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("scatter_nd");
  op_.AddInput(data);
  op_.AddInput(indices);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sequence_last(data; attrs: sequence_length, use_sequence_length, axis)
inline std::vector<NDArray> sequence_last(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sequence_last");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sequence_mask(data; attrs: sequence_length, use_sequence_length, value, axis)
inline std::vector<NDArray> sequence_mask(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sequence_mask");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sequence_reverse(data; attrs: sequence_length, use_sequence_length, axis)
inline std::vector<NDArray> sequence_reverse(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sequence_reverse");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// shape_array(data)
inline std::vector<NDArray> shape_array(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("shape_array");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// shuffle(data)
inline std::vector<NDArray> shuffle(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("shuffle");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sigmoid(data)
inline std::vector<NDArray> sigmoid(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sigmoid");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sign(x)
inline std::vector<NDArray> sign(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sign");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sin(x)
inline std::vector<NDArray> sin(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sin");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sinh(x)
inline std::vector<NDArray> sinh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sinh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// size_array(data)
inline std::vector<NDArray> size_array(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("size_array");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// slice(data; attrs: begin, end, step)
inline std::vector<NDArray> slice(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("slice");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// slice_axis(data; attrs: axis, begin, end)
inline std::vector<NDArray> slice_axis(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("slice_axis");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// slice_like(data, shape_like; attrs: axes)
inline std::vector<NDArray> slice_like(
    const NDArray &data, const NDArray &shape_like,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("slice_like");
  op_.AddInput(data);
  op_.AddInput(shape_like);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// slogdet(A)
inline std::vector<NDArray> slogdet(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("slogdet");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// smooth_l1(data; attrs: scalar)
inline std::vector<NDArray> smooth_l1(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("smooth_l1");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softmax(data; attrs: axis, temperature, length)
inline std::vector<NDArray> softmax(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softmax");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softmax_cross_entropy(data, label)
inline std::vector<NDArray> softmax_cross_entropy(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softmax_cross_entropy");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softmax_output(data, label; attrs: grad_scale, ignore_label, use_ignore, multi_output, preserve_shape, normalization, out_grad, smooth_alpha)
inline std::vector<NDArray> softmax_output(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softmax_output");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softmin(data; attrs: axis)
inline std::vector<NDArray> softmin(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softmin");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softrelu(data)
inline std::vector<NDArray> softrelu(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softrelu");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// softsign(data)
inline std::vector<NDArray> softsign(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("softsign");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sort(data; attrs: axis, is_ascend)
inline std::vector<NDArray> sort(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sort");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// space_to_depth(data; attrs: block_size)
inline std::vector<NDArray> space_to_depth(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("space_to_depth");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// spatial_transformer(data, loc; attrs: target_shape, transform_type, sampler_type, cudnn_off)
inline std::vector<NDArray> spatial_transformer(
    const NDArray &data, const NDArray &loc,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("spatial_transformer");
  op_.AddInput(data);
  op_.AddInput(loc);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// split(data; attrs: num_outputs, axis, squeeze_axis)
inline std::vector<NDArray> split(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("split");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sqrt(x)
inline std::vector<NDArray> sqrt(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sqrt");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// square(x)
inline std::vector<NDArray> square(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("square");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// squeeze(data; attrs: axis)
inline std::vector<NDArray> squeeze(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("squeeze");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// stop_gradient(data)
inline std::vector<NDArray> stop_gradient(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("stop_gradient");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// sum_axis(data; attrs: axis, keepdims, exclude)
inline std::vector<NDArray> sum_axis(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("sum_axis");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// svd(A)
inline std::vector<NDArray> svd(
    const NDArray &A,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("svd");
  op_.AddInput(A);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// svm_output(data, label; attrs: margin, regularization_coefficient, use_linear)
inline std::vector<NDArray> svm_output(
    const NDArray &data, const NDArray &label,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("svm_output");
  op_.AddInput(data);
  op_.AddInput(label);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// swapaxes(data; attrs: dim1, dim2)
inline std::vector<NDArray> swapaxes(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("swapaxes");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// swish(data)
inline std::vector<NDArray> swish(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("swish");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// take(a, indices; attrs: axis, mode)
inline std::vector<NDArray> take(
    const NDArray &a, const NDArray &indices,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("take");
  op_.AddInput(a);
  op_.AddInput(indices);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// tan(x)
inline std::vector<NDArray> tan(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("tan");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// tanh(x)
inline std::vector<NDArray> tanh(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("tanh");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// tile(data; attrs: reps)
inline std::vector<NDArray> tile(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("tile");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// topk(data; attrs: axis, k, ret_typ, is_ascend, dtype)
inline std::vector<NDArray> topk(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("topk");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// transpose(data; attrs: axes)
inline std::vector<NDArray> transpose(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("transpose");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// trunc(x)
inline std::vector<NDArray> trunc(
    const NDArray &x,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("trunc");
  op_.AddInput(x);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// unravel_index(data; attrs: shape)
inline std::vector<NDArray> unravel_index(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("unravel_index");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// upsampling(data; attrs: scale, sample_type, num_filter, multi_input_mode, workspace)
inline std::vector<NDArray> upsampling(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("upsampling");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// where(condition, x, y)
inline std::vector<NDArray> where(
    const NDArray &condition, const NDArray &x, const NDArray &y,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("where");
  op_.AddInput(condition);
  op_.AddInput(x);
  op_.AddInput(y);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

// zeros_like(data)
inline std::vector<NDArray> zeros_like(
    const NDArray &data,
    const std::vector<std::pair<std::string, std::string>> &attrs = {}) {
  Operator op_("zeros_like");
  op_.AddInput(data);
  for (const auto &kv : attrs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

}  // namespace op
}  // namespace cpp
}  // namespace mxtpu

#endif  // MXNET_TPU_CPP_OPS_HPP_
